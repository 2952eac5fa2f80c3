"""Simple Convolution (2-D 3x3 stencil) — Adjacent Access pattern, 2-D.

Row-partitioned image; each shard needs one halo row from each
neighbor.  D-mode: two collective_permutes (up + down).  The local
stencil math matches kernels/stencil.py (which is the TPU Pallas kernel
for this hot-spot); the oracle is kernels.ref.stencil2d_ref.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels.ref import stencil2d_ref

PATTERN = "adjacent"
K = 3


def reference(img: np.ndarray, kern: np.ndarray) -> np.ndarray:
    return np.asarray(stencil2d_ref(jnp.asarray(img), jnp.asarray(kern)))


def _stencil_padded(x, kern):
    """x (h+2, W) incl. top/bottom halo rows -> (h, W) same-padded cols."""
    h = x.shape[0] - 2
    W = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (1, 1)))
    acc = jnp.zeros((h, W), x.dtype)
    for dy in range(K):
        for dx in range(K):
            acc = acc + kern[dy, dx] * \
                jax.lax.dynamic_slice(xp, (dy, dx), (h, W))
    return acc


def default_size(n_devices: int) -> int:
    return 1024 * max(1, int(np.sqrt(n_devices)) * 2)   # Table 2: 1024->2048


def make_umode(mesh):
    sh = NamedSharding(mesh, P("dev", None))

    def fn(img, kern):
        img = jax.lax.with_sharding_constraint(img, sh)
        return _stencil_padded(jnp.pad(img, ((1, 1), (0, 0))), kern)
    return jax.jit(fn, out_shardings=sh)


def make_dmode(mesh):
    def local(img, kern):
        n = jax.lax.axis_size("dev")
        idx = jax.lax.axis_index("dev")
        down = [(i, (i + 1) % n) for i in range(n)]
        up = [(i, (i - 1) % n) for i in range(n)]
        top_halo = jax.lax.ppermute(img[-1:], "dev", perm=down)
        bot_halo = jax.lax.ppermute(img[:1], "dev", perm=up)
        top_halo = jnp.where(idx == 0, jnp.zeros_like(top_halo), top_halo)
        bot_halo = jnp.where(idx == n - 1, jnp.zeros_like(bot_halo), bot_halo)
        return _stencil_padded(jnp.concatenate([top_halo, img, bot_halo]),
                               kern)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P("dev", None), P(None, None)),
                       out_specs=P("dev", None), check_vma=False)
    return jax.jit(fn)


def make_args(width: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return (rng.normal(0, 1, (width, width)).astype(np.float32),
            rng.normal(0, 1, (K, K)).astype(np.float32))
