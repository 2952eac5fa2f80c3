"""Gradient Descent (data-parallel linear regression) — Gather pattern.

Each device computes the gradient over its mini-batch shard; the
gradients are averaged — the gather/all-reduce every DP trainer performs
each step (the paper calls out GD as the canonical Gather workload and a
cross-GPU interconnect stress test).  Several steps run inside one
program so the Gather repeats on the timeline.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

PATTERN = "gather"
FEATURES = 256
STEPS = 8
LR = 0.05


def _grad(X, y, w, n):
    """Least-squares gradient over the rows held, at full f32 precision
    (the TPU's default matmul rounds f32 operands to bf16)."""
    hi = jax.lax.Precision.HIGHEST
    r = jnp.matmul(X, w, precision=hi) - y
    return jnp.matmul(X.T, r, precision=hi) / n


def reference(X: np.ndarray, y: np.ndarray, w: np.ndarray) -> np.ndarray:
    w = w.copy().astype(np.float64)
    for _ in range(STEPS):
        g = X.T.astype(np.float64) @ (X.astype(np.float64) @ w
                                      - y.astype(np.float64)) / X.shape[0]
        w = w - LR * g
    return w.astype(X.dtype)


def default_size(n_devices: int) -> int:
    return 64 * 1024 * max(1, n_devices)   # Table 2: 256K/1M params scaled


def make_umode(mesh):
    sh = NamedSharding(mesh, P("dev", None))

    def fn(X, y, w):
        X = jax.lax.with_sharding_constraint(X, sh)

        def step(w, _):
            g = _grad(X, y, w, X.shape[0])
            return w - LR * g, None
        w, _ = jax.lax.scan(step, w, None, length=STEPS)
        return w
    return jax.jit(fn)


def make_dmode(mesh):
    def local(X, y, w):
        n = X.shape[0] * jax.lax.axis_size("dev")

        def step(w, _):
            g_local = _grad(X, y, w, n)
            g = jax.lax.psum(g_local, "dev")         # THE gather
            return w - LR * g, None
        w, _ = jax.lax.scan(step, w, None, length=STEPS)
        return w
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P("dev", None), P("dev"), P(None)),
                       out_specs=P(None), check_vma=False)
    return jax.jit(fn)


def make_args(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    X = rng.normal(0, 1, (n, FEATURES)).astype(np.float32)
    w_true = rng.normal(0, 1, FEATURES).astype(np.float32)
    y = X @ w_true + rng.normal(0, 0.01, n).astype(np.float32)
    w0 = np.zeros(FEATURES, np.float32)
    return X, y, w0
