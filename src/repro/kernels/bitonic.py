"""Bitonic compare-exchange stage — Pallas TPU kernel (MGMark BS workload).

One bitonic stage with compare distance ``dist`` inside a contiguous
block: partner(i) = i XOR dist; the ascending/descending direction flips
with bit ``size`` of the global index.  Stages with dist >= block size
are the *cross-shard* part of the Irregular pattern and are handled at
the jnp/shard_map level (patterns/bs.py) — this kernel owns the dense
in-VMEM stages, which dominate op count (log^2 factor).

Vectorized TPU formulation: with the block viewed as (block/2/dist rows
of [2*dist]), the exchange is a reshape to (?, 2, dist), a min/max pair
and a reshape back — no per-element scatter, VPU-friendly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .mode import resolve_interpret


def _bitonic_kernel(x_ref, o_ref, *, dist: int, size: int, block: int):
    i = pl.program_id(0)
    x = x_ref[...]
    v = x.reshape(block // (2 * dist), 2, dist)
    lo = jnp.minimum(v[:, 0], v[:, 1])
    hi = jnp.maximum(v[:, 0], v[:, 1])
    # direction: ascending iff (global_index & size) == 0
    base = i * block + jax.lax.broadcasted_iota(
        jnp.int32, (block // (2 * dist), dist), 0) * 2 * dist
    asc = (base & size) == 0
    first = jnp.where(asc, lo, hi)
    second = jnp.where(asc, hi, lo)
    o_ref[...] = jnp.stack([first, second], axis=1).reshape(block)


@functools.partial(jax.jit, static_argnames=("dist", "size", "block",
                                             "interpret"))
def bitonic_stage(x, dist: int, size: int, block: int = 2048,
                  interpret: bool = None):
    """One compare-exchange stage. x (L,), dist < block <= L, L % block == 0.
    ``size`` is the bitonic run length of the enclosing phase."""
    interpret = resolve_interpret(interpret)
    L = x.shape[0]
    block = min(block, L)
    assert dist < block and L % block == 0 and block % (2 * dist) == 0
    return pl.pallas_call(
        functools.partial(_bitonic_kernel, dist=dist, size=size, block=block),
        grid=(L // block,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((L,), x.dtype),
        interpret=interpret,
    )(x)
