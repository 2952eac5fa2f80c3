"""Mamba2 SSD intra-chunk kernel — Pallas TPU.

The SSD hot-spot is the per-chunk quadratic form (Dao & Gu 2024, alg. 2):

    att[i,j] = (C_i . B_j) * exp(cs_i - cs_j) * dt_j   for i >= j
    Y_diag   = att @ X                    (Q x Q) @ (Q x P)
    S_chunk  = (B * exp(cs_Q - cs) * dt)^T @ X          (N x P)

One grid cell computes one (batch*chunk, head) tile entirely in VMEM —
Q=256, N<=128, P=64 gives a ~0.5 MB working set, and both matmuls are
MXU-shaped.  The inter-chunk recurrence (tiny: one (N,P) state per head
per chunk) stays in jnp (`repro.models.ssm.ssd_reference`) — it is
O(L/Q) sequential and bandwidth-trivial.

Inputs are pre-arranged by ops.ssd_chunk:
    x  (R, H, Q, P)   dt (R, H, Q)   cs (R, H, Q)   B/C (R, H, Q, N)
with R = batch * n_chunks, cs = inclusive cumsum of dt*A within chunk.
Outputs: y_diag (R, H, Q, P), states (R, H, N, P).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mode import resolve_interpret


def _ssd_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref, y_ref, s_ref):
    x = x_ref[0, 0].astype(jnp.float32)       # (Q,P)
    dt = dt_ref[0, 0].astype(jnp.float32)     # (Q,1) -- padded trailing dim
    cs = cs_ref[0, 0].astype(jnp.float32)     # (Q,1)
    Bm = b_ref[0, 0].astype(jnp.float32)      # (Q,N)
    Cm = c_ref[0, 0].astype(jnp.float32)      # (Q,N)
    Q = x.shape[0]
    # decay matrix exp(cs_i - cs_j), lower-triangular
    seg = cs - cs.reshape(1, Q)               # (Q,Q) i,j
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    decay = jnp.where(ii >= jj, jnp.exp(seg), 0.0)
    att = (Cm @ Bm.T) * decay * dt.reshape(1, Q)
    y_ref[0, 0] = (att @ x).astype(y_ref.dtype)
    # chunk state: sum_j B_j dt_j exp(cs_last - cs_j) x_j
    w = jnp.exp(cs[Q - 1] - cs) * dt          # (Q,1)
    s_ref[0, 0] = ((Bm * w).T @ x).astype(s_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_kernel(x, dt, cs, Bm, Cm, interpret: bool = None):
    """x (R,H,Q,P); dt/cs (R,H,Q); Bm/Cm (R,H,Q,N) ->
    (y_diag (R,H,Q,P) f32, states (R,H,N,P) f32)."""
    interpret = resolve_interpret(interpret)
    R, H, Q, P = x.shape
    N = Bm.shape[-1]
    dt2 = dt[..., None]                        # (R,H,Q,1)
    cs2 = cs[..., None]
    grid = (R, H)
    y, s = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda r, h: (r, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda r, h: (r, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda r, h: (r, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda r, h: (r, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda r, h: (r, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda r, h: (r, h, 0, 0)),
            pl.BlockSpec((1, 1, N, P), lambda r, h: (r, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, H, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((R, H, N, P), jnp.float32),
        ],
        interpret=interpret,
    )(x, dt2, cs2, Bm, Cm)
    return y, s
