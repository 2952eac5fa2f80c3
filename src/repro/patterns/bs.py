"""Bitonic Sort — Irregular pattern.

Every stage touches the whole address space; stages whose compare
distance crosses the shard boundary are pairwise shard exchanges.

D-mode decomposition for L elements on m shards (Ll = L/m local):
  * dist >= Ll: partner shard = mine XOR (dist/Ll); ONE collective_permute
    of the whole local array per stage; direction/keep side are static
    per (stage, shard) — computed from the shard index;
  * dist <  Ll: fully local vectorized compare-exchange
    (kernels/bitonic.py owns this on TPU; jnp ref here).

The cross-shard stages are the Irregular traffic the paper measures: the
full array crosses the fabric O(log^2 m) times.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels.ref import bitonic_stage_ref, bitonic_sort_ref

PATTERN = "irregular"


def reference(x: np.ndarray) -> np.ndarray:
    return np.sort(x)


def default_size(n_devices: int) -> int:
    return 32 * 1024 * max(1, n_devices)           # Table 2: 32K -> 128K


def make_umode(mesh):
    sh = NamedSharding(mesh, P("dev"))

    def fn(x):
        x = jax.lax.with_sharding_constraint(x, sh)
        return bitonic_sort_ref(x)
    return jax.jit(fn, out_shardings=sh)


def make_dmode(mesh):
    m = mesh.shape["dev"]

    def local(x):
        Ll = x.shape[0]
        idx = jax.lax.axis_index("dev")
        L = Ll * m
        size = 2
        while size <= L:
            dist = size // 2
            while dist >= 1:
                if dist >= Ll:
                    shard_dist = dist // Ll
                    partner = [(i, i ^ shard_dist) for i in range(m)]
                    other = jax.lax.ppermute(x, "dev", perm=partner)
                    pidx = idx ^ shard_dist
                    # ascending iff (global index & size)==0: bit above the
                    # offset -> a bit of the shard id
                    asc = (idx & (size // Ll)) == 0
                    low_side = idx < pidx
                    take_min = jnp.logical_not(jnp.logical_xor(low_side, asc))
                    x = jnp.where(take_min, jnp.minimum(x, other),
                                  jnp.maximum(x, other))
                else:
                    # direction bit of the *global* index: if size <= Ll it
                    # varies inside the shard (local ref handles it); when
                    # size > Ll it is constant here -> pass shard-adjusted size
                    if size < Ll:
                        # direction bit is inside the local offset
                        x = bitonic_stage_ref(x, dist, size)
                    else:
                        # direction bit is a shard-id bit: constant here
                        asc = (idx & (size // Ll)) == 0
                        x = jnp.where(asc, _stage_fixed(x, dist, True),
                                      _stage_fixed(x, dist, False))
                dist //= 2
            size *= 2
        return x

    fn = jax.shard_map(local, mesh=mesh, in_specs=(P("dev"),),
                       out_specs=P("dev"), check_vma=False)
    return jax.jit(fn)


def _stage_fixed(x, dist: int, ascending: bool):
    """Compare-exchange stage with a single fixed direction."""
    L = x.shape[0]
    v = x.reshape(L // (2 * dist), 2, dist)
    lo = jnp.minimum(v[:, 0], v[:, 1])
    hi = jnp.maximum(v[:, 0], v[:, 1])
    pair = (lo, hi) if ascending else (hi, lo)
    return jnp.stack(pair, axis=1).reshape(L)


def make_args(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return (rng.normal(0, 1, n).astype(np.float32),)
