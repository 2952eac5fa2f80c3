"""KMeans (one assignment + partial-sum iteration) — Partitioned Data.

Points are partitioned; every device computes distances/assignments for
its slice and local per-cluster partial sums.  Like the paper's version
the centroid update is a host-side reduction — devices never exchange
points, making this Partitioned despite the iteration structure.  Memory
-intensive and cache-reuse-heavy (the paper's contrast with AES).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

PATTERN = "partitioned"
FEATURES = 32
CLUSTERS = 16


def _mm(a, b):
    """f32 matmul at full precision: the TPU's default rounds f32 operands
    to bf16, which flips near-tie assignments against the f32 oracle."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _assign_and_sum(pts, cent):
    """pts (n,F), cent (K,F) -> (sums (K,F), counts (K,), assign (n,))."""
    d2 = (jnp.sum(pts * pts, -1, keepdims=True)
          - 2.0 * _mm(pts, cent.T) + jnp.sum(cent * cent, -1)[None])
    a = jnp.argmin(d2, axis=-1)
    onehot = jax.nn.one_hot(a, cent.shape[0], dtype=pts.dtype)
    return _mm(onehot.T, pts), jnp.sum(onehot, axis=0), a.astype(jnp.int32)


def reference(points: np.ndarray, centroids: np.ndarray):
    d2 = ((points[:, None, :] - centroids[None]) ** 2).sum(-1)
    a = d2.argmin(-1)
    sums = np.zeros_like(centroids)
    counts = np.zeros(centroids.shape[0])
    for k in range(centroids.shape[0]):
        sel = points[a == k]
        sums[k] = sel.sum(0) if len(sel) else 0
        counts[k] = len(sel)
    new = sums / np.maximum(counts[:, None], 1)
    return new.astype(points.dtype)


def default_size(n_devices: int) -> int:
    return 32 * 1024 * max(1, n_devices)            # Table 2: 32K pts x devs


def make_umode(mesh):
    sh = NamedSharding(mesh, P("dev", None))

    def fn(pts, cent):
        pts = jax.lax.with_sharding_constraint(pts, sh)
        sums, counts, _ = _assign_and_sum(pts, cent)
        return sums / jnp.maximum(counts[:, None], 1)
    return jax.jit(fn)


def make_dmode(mesh):
    def local(pts, cent):
        sums, counts, _ = _assign_and_sum(pts, cent)
        # host-reduction analog: one small psum of (K,F)+(K,) partials
        sums = jax.lax.psum(sums, "dev")
        counts = jax.lax.psum(counts, "dev")
        return sums / jnp.maximum(counts[:, None], 1)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P("dev", None), P(None, None)),
                       out_specs=P(None, None), check_vma=False)
    return jax.jit(fn)


def make_args(n_points: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    pts = rng.normal(0, 1, (n_points, FEATURES)).astype(np.float32)
    cent = rng.normal(0, 1, (CLUSTERS, FEATURES)).astype(np.float32)
    return pts, cent
