"""Causal flash attention — Pallas TPU kernel.

TPU adaptation of FlashAttention: the online-softmax tiling is reshaped
for the MXU/VMEM hierarchy rather than CUDA warps/shared-memory:

* grid = (batch, q_heads, Sq/bq, Skv/bk); the kv axis is the innermost
  (sequential) grid dim, so the (m, l, acc) running state lives in VMEM
  scratch across kv steps — no HBM spills between tiles;
* block shapes are (bq, head_dim) / (bk, head_dim) with bq=bk=128 —
  MXU-aligned (128x128 systolic tiles);
* GQA is handled in the k/v BlockSpec index maps (q head h reads kv head
  h // group_size) — zero-copy, no repeated KV in HBM;
* causal: kv tiles strictly above the diagonal are skipped via pl.when
  (the mosaic grid still visits them, but no FLOPs/VMEM traffic happen).

f32 accumulation regardless of input dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mode import resolve_interpret

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, bq: int, bk: int, causal: bool):
    i = pl.program_id(2)                     # q tile
    j = pl.program_id(3)                     # kv tile (innermost, sequential)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    should_run = True
    if causal:
        should_run = (j * bk) <= (i * bq + bq - 1)   # tile intersects lower tri

    @pl.when(should_run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = (q @ k.T) * scale                          # (bq, bk)
        if causal:
            qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qi >= kj, s, NEG_INF)
        m_prev = m_ref[...]                            # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                         # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                # (bq, 1)
        l_ref[...] = alpha * l_ref[...] + p.sum(-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + p @ v
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, causal: bool = True, bq: int = DEFAULT_BQ,
                    bk: int = DEFAULT_BK, interpret: bool = None):
    """q (B,Sq,H,hd); k/v (B,Skv,K,hd); H % K == 0 -> out (B,Sq,H,hd)."""
    interpret = resolve_interpret(interpret)
    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    G = H // K
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    qt = jnp.moveaxis(q, 1, 2)               # (B,H,Sq,hd)
    kt = jnp.moveaxis(k, 1, 2)               # (B,K,Skv,hd)
    vt = jnp.moveaxis(v, 1, 2)
    grid = (B, H, Sq // bq, Skv // bk)
    kernel = functools.partial(_flash_kernel, scale=1.0 / math.sqrt(hd),
                               bq=bq, bk=bk, causal=causal)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)           # (B,Sq,H,hd)
