"""FIR filter — Adjacent Access pattern.

Causal FIR over a partitioned signal: each shard needs the previous
shard's last (taps-1) samples.  D-mode moves exactly that halo with one
collective_permute; U-mode lets GSPMD discover the same halo from a
global convolution.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

PATTERN = "adjacent"
TAPS = 16


def _fir_local(x, taps):
    """x already left-padded with (T-1) halo samples: y_i = sum taps_j *
    x[i + T-1 - j]."""
    T = taps.shape[0]
    n = x.shape[0] - (T - 1)
    y = jnp.zeros(n, x.dtype)
    for j in range(T):                               # static taps
        y = y + taps[j] * jax.lax.dynamic_slice(x, (T - 1 - j,), (n,))
    return y


def reference(x: np.ndarray, taps: np.ndarray) -> np.ndarray:
    return np.convolve(x, taps, mode="full")[:x.shape[0]].astype(x.dtype)


def default_size(n_devices: int) -> int:
    return 64 * 1024 * max(1, n_devices)            # Table 2: 64K SP samples


def make_umode(mesh):
    sh = NamedSharding(mesh, P("dev"))

    def fn(x, taps):
        x = jax.lax.with_sharding_constraint(x, sh)
        xp = jnp.pad(x, (TAPS - 1, 0))
        return _fir_local(xp, taps)
    return jax.jit(fn, out_shardings=sh)


def make_dmode(mesh):
    def local(x, taps):
        T = taps.shape[0]
        # halo: last T-1 samples of the LEFT neighbor (ring, shard 0 zero)
        n = jax.lax.axis_size("dev")
        idx = jax.lax.axis_index("dev")
        tail = x[-(T - 1):]
        halo = jax.lax.ppermute(tail, "dev",
                                perm=[(i, (i + 1) % n) for i in range(n)])
        halo = jnp.where(idx == 0, jnp.zeros_like(halo), halo)
        return _fir_local(jnp.concatenate([halo, x]), taps)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(P("dev"), P(None)),
                       out_specs=P("dev"), check_vma=False)
    return jax.jit(fn)


def make_args(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return (rng.normal(0, 1, n).astype(np.float32),
            rng.normal(0, 1, TAPS).astype(np.float32))
