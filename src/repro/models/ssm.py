"""Mamba2 (SSD — state-space duality) blocks, pure JAX reference.

The SSD chunked algorithm (Dao & Gu, 2024) maps the selective-state-space
recurrence onto matmuls the MXU can eat:

    S_t = exp(dt_t * A_h) * S_{t-1} + dt_t * B_t x_t^T        (state: N x P)
    y_t = C_t . S_t + D_h * x_t

split the sequence into chunks of Q tokens; within a chunk the kernel is
a (masked) quadratic form — matmuls; across chunks a cheap associative
recurrence over chunk states.  The intra-chunk part is the compute
hot-spot and has a Pallas TPU kernel (``repro.kernels.ssd``); this module
is the oracle and the CPU/dry-run lowering path.

Shapes: x (B,L,H,P)  dt (B,L,H)  A (H,)  B/C (B,L,G,N) with G==1 here.
All SSD math is f32 regardless of model dtype (exponentials).
"""
from __future__ import annotations

import math
import typing

import jax
import jax.numpy as jnp

from .layers import Params, dense_init, rms_norm, split_rngs


# --------------------------------------------------------------------------
# core SSD scan (reference)
# --------------------------------------------------------------------------

def ssd_reference(x, dt, A, Bm, Cm, chunk: int = 256, initial_state=None,
                  return_state: bool = False):
    """Chunked SSD. x (B,L,H,P) dt (B,L,H) A (H,) Bm/Cm (B,L,G,N)."""
    Bsz, L, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(chunk, L)
    pad = (-L) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad)) + ((0, 0),))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Lp = L + pad
    C = Lp // Q
    xc = x.reshape(Bsz, C, Q, H, P).astype(jnp.float32)
    dtc = dt.reshape(Bsz, C, Q, H).astype(jnp.float32)
    Bc = Bm.reshape(Bsz, C, Q, G, N).astype(jnp.float32)
    Cc = Cm.reshape(Bsz, C, Q, G, N).astype(jnp.float32)
    A = A.astype(jnp.float32)

    dA = dtc * A[None, None, None, :]                      # (B,C,Q,H) <= 0
    cs = jnp.cumsum(dA, axis=2)                            # inclusive cumsum

    # ---- intra-chunk (diagonal blocks) --------------------------------
    # att[b,c,h,i,j] = exp(cs_i - cs_j) * (C_i . B_j) * dt_j   (i >= j)
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]      # (B,C,Q,Q,H) i,j
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.where(mask[None, None, :, :, None], jnp.exp(seg), 0.0)
    qk = jnp.einsum("bcign,bcjgn->bcijg", Cc, Bc)          # (B,C,Q,Q,G)
    hpg = H // G
    att = (qk[..., :, None] *
           decay.reshape(*decay.shape[:-1], G, hpg)
           ).reshape(Bsz, C, Q, Q, H)
    att = att * dtc[:, :, None, :, :]                      # dt_j
    y_diag = jnp.einsum("bcijh,bcjhp->bcihp", att, xc)

    # ---- chunk states --------------------------------------------------
    decay_to_end = jnp.exp(cs[:, :, -1:, :] - cs)          # (B,C,Q,H)
    bdx = Bc[:, :, :, :, None, :] \
        .repeat(hpg, axis=4).reshape(Bsz, C, Q, H, N)      # (B,C,Q,H,N)
    states = jnp.einsum("bcqhn,bcqh,bcqhp->bchnp",
                        bdx, decay_to_end * dtc, xc)        # (B,C,H,N,P)

    # ---- inter-chunk recurrence ----------------------------------------
    chunk_decay = jnp.exp(cs[:, :, -1, :])                 # (B,C,H)
    s0 = initial_state.astype(jnp.float32) if initial_state is not None \
        else jnp.zeros((Bsz, H, N, P), jnp.float32)

    def step(s, inp):
        d, snew = inp                                       # (B,H),(B,H,N,P)
        s_out = s                                           # state entering chunk
        s = d[:, :, None, None] * s + snew
        return s, s_out

    final, s_in = jax.lax.scan(
        step, s0, (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0)))
    s_in = jnp.moveaxis(s_in, 0, 1)                         # (B,C,H,N,P)

    # ---- off-diagonal (carry-in state) ---------------------------------
    cdx = Cc[:, :, :, :, None, :] \
        .repeat(hpg, axis=4).reshape(Bsz, C, Q, H, N)
    y_off = jnp.einsum("bcqhn,bchnp,bcqh->bcqhp",
                       cdx, s_in, jnp.exp(cs))
    y = (y_diag + y_off).reshape(Bsz, Lp, H, P)[:, :L]
    if return_state:
        return y, final
    return y


def ssd_scan(x, dt, A, Bm, Cm, chunk: int = 256, initial_state=None,
             return_state: bool = False, bh=None):
    """Memory-lean SSD — identical math to :func:`ssd_reference`, but the
    O(Q^2) intra-chunk tile is built for ONE chunk at a time.

    Two passes:
      1. chunk states (no Q^2 tensor) + the tiny inter-chunk scan;
      2. `lax.map` over chunks for the quadratic part, with the chunk
         body `jax.checkpoint`'ed so the backward pass rebuilds each
         (B,Q,Q,H) tile instead of stacking all C of them — the
         difference between ~30 MB and ~470 GB live per device at
         zamba2-7b/train_4k scale.

    Requires G == 1 (all assigned SSM archs).  Equality with
    ssd_reference is asserted in tests/test_kernels.py.
    """
    Bsz, L, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert G == 1, "ssd_scan assumes a single B/C group"
    Q = min(chunk, L)
    pad = (-L) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Lp = L + pad
    C = Lp // Q
    xc = x.reshape(Bsz, C, Q, H, P).astype(jnp.float32)
    dtc = dt.reshape(Bsz, C, Q, H).astype(jnp.float32)
    Bc = Bm.reshape(Bsz, C, Q, N).astype(jnp.float32)
    Cc = Cm.reshape(Bsz, C, Q, N).astype(jnp.float32)
    A = A.astype(jnp.float32)

    if bh is not None:
        # pin (batch -> dp, heads -> model) on the big SSD tensors: GSPMD
        # otherwise drops the batch sharding at the chunk-map boundary
        # and replicates full-batch tiles per device (§Perf zamba2 it.3)
        xc = bh(xc, 0, 3)
        dtc = bh(dtc, 0, 3)
    dA = dtc * A[None, None, None, :]
    cs = jnp.cumsum(dA, axis=2)                            # (B,C,Q,H)

    # ---- pass 1: chunk states (linear in Q) + inter-chunk scan --------
    w_end = jnp.exp(cs[:, :, -1:, :] - cs) * dtc           # (B,C,Q,H)
    states = jnp.einsum("bcqn,bcqh,bcqhp->bchnp", Bc, w_end, xc)
    if bh is not None:
        states = bh(states, 0, 2)
    chunk_decay = jnp.exp(cs[:, :, -1, :])                 # (B,C,H)
    s0 = initial_state.astype(jnp.float32) if initial_state is not None \
        else jnp.zeros((Bsz, H, N, P), jnp.float32)

    def step(s, inp):
        d, snew = inp
        return d[:, :, None, None] * s + snew, s

    final, s_in = jax.lax.scan(
        step, s0, (jnp.moveaxis(chunk_decay, 1, 0),
                   jnp.moveaxis(states, 1, 0)))
    s_in = jnp.moveaxis(s_in, 0, 1)                        # (B,C,H,N,P)

    if bh is not None:
        s_in = bh(s_in, 0, 2)

    # ---- pass 2: per-chunk quadratic tile, one chunk live at a time ----
    @jax.checkpoint
    def chunk_fn(args):
        cs_c, dt_c, x_c, b_c, c_c, sin_c = args
        if bh is not None:
            cs_c = bh(cs_c, 0, 2)
            x_c = bh(x_c, 0, 2)
        seg = cs_c[:, :, None, :] - cs_c[:, None, :, :]    # (B,Q,Q,H)
        ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        decay = jnp.where((ii >= jj)[None, :, :, None], jnp.exp(seg), 0.0)
        qk = jnp.einsum("bin,bjn->bij", c_c, b_c)          # (B,Q,Q)
        att = qk[..., None] * decay * dt_c[:, None, :, :]
        if bh is not None:
            att = bh(att, 0, 3)
        y_d = jnp.einsum("bijh,bjhp->bihp", att, x_c)
        y_o = jnp.einsum("bqn,bhnp->bqhp", c_c, sin_c) * \
            jnp.exp(cs_c)[..., None]
        y_c = y_d + y_o
        if bh is not None:
            y_c = bh(y_c, 0, 2)
        return y_c

    args = tuple(jnp.moveaxis(a, 1, 0) for a in
                 (cs, dtc, xc, Bc, Cc, s_in))
    y = jax.lax.map(chunk_fn, args)                        # (C,B,Q,H,P)
    y = jnp.moveaxis(y, 0, 1).reshape(Bsz, Lp, H, P)[:, :L]
    if return_state:
        return y, final
    return y


def ssd_decode_step(state, x, dt, A, Bm, Cm):
    """One-token SSD update.  state (B,H,N,P); x (B,H,P); dt (B,H);
    Bm/Cm (B,G,N). Returns (y (B,H,P), new_state)."""
    B, H, N, P = state.shape
    G = Bm.shape[1]
    hpg = H // G
    x = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    dA = jnp.exp(dt * A[None, :])                          # (B,H)
    Bh = Bm.astype(jnp.float32).repeat(hpg, axis=1)        # (B,H,N)
    Ch = Cm.astype(jnp.float32).repeat(hpg, axis=1)
    new = dA[:, :, None, None] * state + \
        jnp.einsum("bhn,bh,bhp->bhnp", Bh, dt, x)
    y = jnp.einsum("bhn,bhnp->bhp", Ch, new)
    return y, new


# --------------------------------------------------------------------------
# depthwise causal conv (width W, conv over channels of xBC)
# --------------------------------------------------------------------------

def causal_conv(x, w, b):
    """x (B,L,D), w (W,D), b (D,) -> (B,L,D); y_t = sum_i x_{t-W+1+i} w_i."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    y = jnp.zeros_like(x)
    L = x.shape[1]
    for i in range(W):                                      # static, W=4
        y = y + xp[:, i:i + L, :] * w[i]
    return y + b


def conv_step(conv_state, x_t, w, b):
    """conv_state (B,W-1,D); x_t (B,D) -> (y_t (B,D), new_state)."""
    W = w.shape[0]
    full = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # (B,W,D)
    y = jnp.einsum("bwd,wd->bd", full, w) + b
    return y, full[:, 1:, :]


# --------------------------------------------------------------------------
# Mamba2 block
# --------------------------------------------------------------------------

def init_mamba2(rng, cfg) -> Params:
    """Mamba2 block params.  The reference fused in_proj/conv are stored
    as COLUMN BLOCKS (wz | wx | wbc | wdt and conv_x | conv_bc): the same
    linear maps (identical math, identical parameter count), but each
    block's output dim is cleanly TP-shardable — the fused layout slices
    at non-shard-aligned offsets and forces GSPMD to replicate the whole
    SSD inner state (EXPERIMENTS.md §Perf, zamba2 iteration 2)."""
    dt_ = cfg.jnp_dtype
    rs = split_rngs(rng, 8)
    H = cfg.ssm_heads
    gn2 = 2 * cfg.ssm_groups * cfg.ssm_state
    # A in [1, 16] (standard mamba2 init), dt bias ~ softplus^-1(U[1e-3,1e-1])
    a = jnp.exp(jax.random.uniform(rs[2], (H,), jnp.float32,
                                   math.log(1.0), math.log(16.0)))
    u = jax.random.uniform(rs[3], (H,), jnp.float32, 1e-3, 1e-1)
    dt_bias = u + jnp.log(-jnp.expm1(-u))                  # inv softplus
    return {
        "wz": dense_init(rs[0], (cfg.d_model, cfg.d_inner), dt_),
        "wx": dense_init(rs[1], (cfg.d_model, cfg.d_inner), dt_),
        "wbc": dense_init(rs[4], (cfg.d_model, gn2), dt_),
        "wdt": dense_init(rs[5], (cfg.d_model, H), dt_),
        "conv_xw": dense_init(rs[6], (cfg.ssm_conv_width, cfg.d_inner),
                              jnp.float32, scale=0.5),
        "conv_xb": jnp.zeros((cfg.d_inner,), jnp.float32),
        "conv_bcw": dense_init(rs[7], (cfg.ssm_conv_width, gn2),
                               jnp.float32, scale=0.5),
        "conv_bcb": jnp.zeros((gn2,), jnp.float32),
        "A_log": jnp.log(a),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": dt_bias,
        "norm_w": jnp.ones((cfg.d_inner,), dt_),
        "out_proj": dense_init(rs[3], (cfg.d_inner, cfg.d_model), dt_),
    }


@jax.named_scope("ssm")
def mamba2_block(p: Params, x, cfg, initial_state=None,
                 return_state: bool = False, ctx=None):
    """Full-sequence Mamba2 mixer. x (B,L,d) -> y (B,L,d)."""
    B, L, _ = x.shape
    H, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    z = x @ p["wz"]
    xr = (x @ p["wx"]).astype(jnp.float32)                  # (B,L,d_inner)
    bc = (x @ p["wbc"]).astype(jnp.float32)                 # (B,L,2GN)
    dt = x @ p["wdt"]                                       # (B,L,H)
    xs = jax.nn.silu(causal_conv(xr, p["conv_xw"], p["conv_xb"]))
    bc = jax.nn.silu(causal_conv(bc, p["conv_bcw"], p["conv_bcb"]))
    xs = xs.reshape(B, L, H, P)
    Bm = bc[..., :G * N].reshape(B, L, G, N)
    Cm = bc[..., G * N:].reshape(B, L, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    out = ssd_scan(xs, dt, A, Bm, Cm, chunk=cfg.ssm_chunk,
                   initial_state=initial_state, return_state=return_state,
                   bh=(ctx or {}).get("bh"))
    y, final = out if return_state else (out, None)
    y = y + p["D"][None, None, :, None] * xs
    y = y.reshape(B, L, cfg.d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm_w"], cfg.norm_eps)
    y = y @ p["out_proj"]
    if return_state:
        # conv tail: last W-1 pre-activation channels feed future steps
        W = cfg.ssm_conv_width
        raw = jnp.concatenate([xr, bc_raw(x, p)], axis=-1)
        tail = jnp.pad(raw, ((0, 0), (max(0, W - 1 - L), 0),
                             (0, 0)))[:, -(W - 1):]
        return y, {"ssm": final, "conv": tail}
    return y


def bc_raw(x, p):
    return (x @ p["wbc"]).astype(jnp.float32)


@jax.named_scope("ssm")
def mamba2_step(p: Params, x_t, state, cfg):
    """One-token Mamba2 step. x_t (B,d); state {"ssm","conv"}."""
    B = x_t.shape[0]
    H, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    z = x_t @ p["wz"]
    xr = (x_t @ p["wx"]).astype(jnp.float32)
    bc = (x_t @ p["wbc"]).astype(jnp.float32)
    dt = x_t @ p["wdt"]
    xbc = jnp.concatenate([xr, bc], axis=-1)
    w = jnp.concatenate([p["conv_xw"], p["conv_bcw"]], axis=-1)
    b = jnp.concatenate([p["conv_xb"], p["conv_bcb"]], axis=-1)
    xbc_c, conv_new = conv_step(state["conv"], xbc, w, b)
    xbc_c = jax.nn.silu(xbc_c)
    xs = xbc_c[..., :cfg.d_inner].reshape(B, H, P)
    Bm = xbc_c[..., cfg.d_inner:cfg.d_inner + G * N].reshape(B, G, N)
    Cm = xbc_c[..., cfg.d_inner + G * N:].reshape(B, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, ssm_new = ssd_decode_step(state["ssm"], xs, dt, A, Bm, Cm)
    y = y + p["D"][None, :, None] * xs
    y = y.reshape(B, cfg.d_inner).astype(x_t.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm_w"], cfg.norm_eps)
    return y @ p["out_proj"], {"ssm": ssm_new, "conv": conv_new}


def init_mamba_state(cfg, batch: int) -> dict:
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    return {"ssm": jnp.zeros((batch, H, N, P), jnp.float32),
            "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, cfg.conv_dim),
                              jnp.float32)}
