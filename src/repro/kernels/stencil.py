"""2-D convolution stencil — Pallas TPU kernel (MGMark SC workload).

The Adjacent-Access pattern's compute: a KxK stencil over an image tile.
Halo handling is done TPU-style: the input is passed through THREE
BlockSpecs whose index maps point at the tile above, the tile itself and
the tile below (clamped at the edges) — overlapping reads are expressed
as multiple views instead of CUDA-style shared-memory staging.  Columns
keep the full width so only row halos are needed (images are row-major
and W*4B <= VMEM budget for the benchmark sizes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .mode import resolve_interpret


def _stencil_kernel(top_ref, mid_ref, bot_ref, k_ref, o_ref, *,
                    br: int, K: int):
    H = pl.num_programs(0) * br
    i = pl.program_id(0)
    r = K // 2
    top = top_ref[...].astype(jnp.float32)
    mid = mid_ref[...].astype(jnp.float32)
    bot = bot_ref[...].astype(jnp.float32)
    kern = k_ref[...].astype(jnp.float32)
    W = mid.shape[1]
    # assemble (br + 2r, W + 2r) working tile with zero column pads
    stacked = jnp.concatenate([top[-r:], mid, bot[:r]], axis=0)
    # row halos are invalid at the global edges -> zero them
    row_idx = i * br - r + jax.lax.broadcasted_iota(
        jnp.int32, (br + 2 * r, 1), 0)
    stacked = jnp.where((row_idx >= 0) & (row_idx < H), stacked, 0.0)
    padded = jnp.pad(stacked, ((0, 0), (r, r)))
    acc = jnp.zeros((br, W), jnp.float32)
    for dy in range(K):                       # static K (3 or 5)
        for dx in range(K):
            acc = acc + kern[dy, dx] * \
                jax.lax.dynamic_slice(padded, (dy, dx), (br, W))
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def stencil2d(img, kern, block_rows: int = 128, interpret: bool = None):
    """img (H, W), kern (K, K) -> same-padded 2-D convolution (H, W)."""
    interpret = resolve_interpret(interpret)
    H, W = img.shape
    K = kern.shape[0]
    br = min(block_rows, H)
    assert H % br == 0, (H, br)
    n = H // br
    clamp = lambda i: jnp.clip(i, 0, n - 1)
    out = pl.pallas_call(
        functools.partial(_stencil_kernel, br=br, K=K),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((br, W), lambda i: (clamp(i - 1), 0)),
            pl.BlockSpec((br, W), lambda i: (i, 0)),
            pl.BlockSpec((br, W), lambda i: (clamp(i + 1), 0)),
            pl.BlockSpec((K, K), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), img.dtype),
        interpret=interpret,
    )(img, img, img, kern)
    return out
