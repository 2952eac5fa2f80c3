"""Pieces the plain references share: float32 products, optionally seen
through fp8 (the correctness control), and RMSNorm."""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def fp8(x, axis):
    """Round to float8_e4m3fn with one scale per slice along ``axis`` and
    back: what a matrix product computed in fp8 sees of its operand."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def mm(x, w, low: bool):
    """x @ w in float32, or with both operands rounded to fp8 when ``low``."""
    x, w = x.astype(F32), w.astype(F32)
    if low:
        x, w = fp8(x, -1), fp8(w, -2)
    return x @ w


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def tree_init(shapes: dict, leaf):
    """A jitted maker of the weight tree ``shapes`` (name -> shape tuples):
    ``leaf(key, name, shape)`` makes each weight from its own fold of the
    key."""
    is_leaf = lambda x: isinstance(x, tuple)
    paths = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=is_leaf)[0]
    tree = jax.tree_util.tree_structure(shapes, is_leaf=is_leaf)

    def make(key):
        return jax.tree_util.tree_unflatten(tree, [
            leaf(jax.random.fold_in(key, i), path[-1].key, shape)
            for i, (path, shape) in enumerate(paths)])
    return jax.jit(make)
