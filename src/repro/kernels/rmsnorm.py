"""Fused RMSNorm — Pallas TPU kernel.

RMSNorm is bandwidth-bound; XLA usually fuses it already, but a fused
kernel pins the pattern: one pass over (rows, d) tiles in VMEM with the
mean-square reduction and the scale applied in-register, f32 math,
output in the input dtype.  Grid over row blocks; the full feature dim
stays resident (d <= 8192 -> 32 KB/row tile at f32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .mode import resolve_interpret


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def rmsnorm(x, w, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool = None):
    """x (..., d), w (d,) -> rmsnorm(x) * w, fused."""
    interpret = resolve_interpret(interpret)
    shape = x.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=((rows + pad) // br,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, d), x.dtype),
        interpret=interpret,
    )(x2, w)
    return out[:rows].reshape(shape)
