"""Matrix Transpose — Scatter pattern.

Row-partitioned matrix; the transpose scatters every shard's blocks to
every other shard.  D-mode is one explicit all_to_all of (M, Nl, Nl)
blocks + a local block transpose; U-mode states `x.T` with row-sharded
input and output and lets GSPMD materialize the exchange.  (The paper
uses MT to validate LDS/local-memory modeling — here the local transpose
is the VMEM-tiled part and the all_to_all is the fabric part.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

PATTERN = "scatter"


def reference(x: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(x.T)


def default_size(n_devices: int) -> int:
    return 2048 * max(1, int(np.sqrt(n_devices)) * 2)  # Table 2: 2048->4096


def make_umode(mesh):
    sh = NamedSharding(mesh, P("dev", None))

    def fn(x):
        x = jax.lax.with_sharding_constraint(x, sh)
        return x.T
    return jax.jit(fn, out_shardings=sh)


def make_dmode(mesh):
    def local(x):                                  # x (Nl, N) local rows
        m = jax.lax.axis_size("dev")
        Nl = x.shape[0]
        blocks = x.reshape(Nl, m, Nl).transpose(1, 0, 2)   # (m, Nl, Nl)
        recv = jax.lax.all_to_all(blocks, "dev", split_axis=0,
                                  concat_axis=0, tiled=False)
        # recv[p] = block B_pq owned by sender p; Y_q columns block p = B_pq^T
        return jnp.transpose(recv, (2, 0, 1)).reshape(Nl, m * Nl)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(P("dev", None),),
                       out_specs=P("dev", None), check_vma=False)
    return jax.jit(fn)


def make_args(width: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return (rng.normal(0, 1, (width, width)).astype(np.float32),)
