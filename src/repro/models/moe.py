"""Mixture-of-Experts FFN (GShard-style capacity dispatch, scatter-based).

The classic (tokens, experts, capacity) one-hot dispatch tensor is
O(T*E*C) — 2e13 elements for dbrx at train_4k — so we build (E, C)
*index* buffers by scatter instead: O(T*k) routing metadata, O(E*C*d)
activations.  Dropped tokens (beyond capacity) fall into a dump slot and
contribute zero, exactly like GShard with capacity_factor.

Two consumers:
* U-mode (jit/GSPMD): `moe_ffn` runs on the full local token block;
  sharding constraints on the (E, C, d) buffers put experts on the
  "model" mesh axis and GSPMD materializes the all-to-alls.
* D-mode (shard_map): `dispatch`/`combine` are called around explicit
  `jax.lax.all_to_all` over the expert axis — the paper's
  Scatter/Irregular pattern made explicit (see sharding/dmode.py).

This is the paper's "Irregular" collaborative pattern in LM form: every
shard reads/writes token slots across the whole expert space.
"""
from __future__ import annotations

import math
import typing

import jax
import jax.numpy as jnp

from .layers import Params, dense_init, split_rngs


def init_moe(rng, cfg) -> Params:
    d, f, E, dt = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.jnp_dtype
    rs = split_rngs(rng, 4)
    return {
        "router": dense_init(rs[0], (d, E), jnp.float32),
        "wg": dense_init(rs[1], (E, d, f), dt),
        "wu": dense_init(rs[2], (E, d, f), dt),
        "wd": dense_init(rs[3], (E, f, d), dt),
    }


def capacity(tokens: int, cfg) -> int:
    c = math.ceil(tokens * cfg.experts_per_token / cfg.num_experts
                  * cfg.capacity_factor)
    return max(8, int(math.ceil(c / 8) * 8))  # pad to an MXU-friendly size


def route(p: Params, x, cfg):
    """x (T,d) -> (expert_idx (T,k) int32, gate_w (T,k) f32, aux_loss)."""
    logits = (x.astype(jnp.float32) @ p["router"])          # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, expert_idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    # load-balancing aux loss (Switch): E * mean(frac_tokens * frac_probs)
    E = cfg.num_experts
    onehot_top1 = jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32)
    aux = E * jnp.mean(jnp.mean(onehot_top1, axis=0) * jnp.mean(probs, axis=0))
    return expert_idx.astype(jnp.int32), gate_w, aux


def build_dispatch(expert_idx, T: int, E: int, C: int):
    """expert_idx (T,k) -> (dispatch_idx (E,C) int32 in [0..T] where T is
    the zero-pad slot, pos (T*k,) int32 clipped to C, keep (T*k,) bool).

    Token-major flattening keeps each token's k assignments contiguous so
    combine is a reshape+sum, not a scatter-add.
    """
    k = expert_idx.shape[1]
    flat_e = expert_idx.reshape(-1)                         # (T*k,)
    onehot = (flat_e[:, None] == jnp.arange(E)[None, :]).astype(jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)             # exclusive count
    pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = pos < C
    pos_c = jnp.where(keep, pos, C)                         # dump slot
    token_ids = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    buf = jnp.full((E, C + 1), T, jnp.int32)
    buf = buf.at[flat_e, pos_c].set(token_ids, mode="drop")
    return buf[:, :C], pos_c, keep


def expert_ffn(p: Params, xe):
    """xe (E,C,d) -> (E,C,d): per-expert SwiGLU via batched matmul."""
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["wg"]))
    u = jnp.einsum("ecd,edf->ecf", xe, p["wu"])
    return jnp.einsum("ecf,efd->ecd", g * u, p["wd"])


@jax.named_scope("moe")
def moe_ffn(p: Params, x, cfg, ep_constraint=None):
    """Full MoE FFN on a local token block. x (T,d) -> (y (T,d), aux).

    With cfg.moe_groups > 1 dispatch runs per token-group (GShard's
    per-device capacity): the position-in-expert cumsum becomes
    group-local, so under SPMD no cross-shard prefix sums ever happen —
    the fix that removes the per-layer all-reduce avalanche the global
    formulation costs at 1M-token scale (EXPERIMENTS.md §Perf).
    """
    if cfg.moe_groups > 1 and x.shape[0] % cfg.moe_groups == 0:
        return grouped_moe_ffn(p, x, cfg, ep_constraint)
    T, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    C = capacity(T, cfg)
    expert_idx, gate_w, aux = route(p, x, cfg)
    dispatch_idx, pos_c, keep = build_dispatch(expert_idx, T, E, C)
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    xe = jnp.take(x_pad, dispatch_idx, axis=0)              # (E,C,d)
    if ep_constraint is not None:
        xe = ep_constraint(xe)                              # experts -> "model"
    ye = expert_ffn(p, xe)
    if ep_constraint is not None:
        ye = ep_constraint(ye)
    # gather each assignment's output back: rows are token-major
    ye_pad = jnp.concatenate(
        [ye, jnp.zeros((E, 1, d), ye.dtype)], axis=1)       # dump slot reads 0
    flat_e = expert_idx.reshape(-1)
    out_rows = ye_pad[flat_e, pos_c]                        # (T*k, d)
    w = (gate_w.reshape(-1) * keep).astype(out_rows.dtype)
    y = (out_rows * w[:, None]).reshape(T, k, d).sum(axis=1)
    return y.astype(x.dtype), aux


def grouped_moe_ffn(p: Params, x, cfg, ep_constraint=None):
    """Per-group dispatch: x (T,d) viewed as (G_r, T/G_r, d); routing,
    position cumsum and capacity are group-local (vmapped), experts see
    the concatenated slots (E, G_r*C_g, d).  Semantically GShard with
    group = device; drops can differ from the global formulation only
    when a group is locally over-subscribed (same trade GShard makes)."""
    T, d = x.shape
    Gr = cfg.moe_groups
    E, k = cfg.num_experts, cfg.experts_per_token
    Tg = T // Gr
    Cg = capacity(Tg, cfg)
    xg = x.reshape(Gr, Tg, d)

    def route_group(xs):
        expert_idx, gate_w, aux = route(p, xs, cfg)
        dispatch_idx, pos_c, keep = build_dispatch(expert_idx, Tg, E, Cg)
        x_pad = jnp.concatenate([xs, jnp.zeros((1, d), xs.dtype)], axis=0)
        xe = jnp.take(x_pad, dispatch_idx, axis=0)       # (E,Cg,d)
        return xe, (expert_idx, gate_w, pos_c, keep), aux

    xe, meta, aux = jax.vmap(route_group)(xg)            # (Gr,E,Cg,d)
    xe = jnp.swapaxes(xe, 0, 1).reshape(E, Gr * Cg, d)
    if ep_constraint is not None:
        xe = ep_constraint(xe)
    ye = expert_ffn(p, xe)
    if ep_constraint is not None:
        ye = ep_constraint(ye)
    ye = jnp.swapaxes(ye.reshape(E, Gr, Cg, d), 0, 1)    # (Gr,E,Cg,d)

    def combine_group(ye_g, meta_g):
        return combine_local(ye_g, meta_g, cfg)
    y = jax.vmap(combine_group)(ye, meta)                # (Gr,Tg,d)
    return y.reshape(T, d).astype(x.dtype), jnp.mean(aux)


# --------------------------------------------------------------------------
# D-mode building blocks (used inside shard_map; see sharding/dmode.py)
# --------------------------------------------------------------------------

def dispatch_local(p: Params, x, cfg, C: int):
    """Route a local token shard and build its (E, C, d) send buffer."""
    T, d = x.shape
    E = cfg.num_experts
    expert_idx, gate_w, aux = route(p, x, cfg)
    dispatch_idx, pos_c, keep = build_dispatch(expert_idx, T, E, C)
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    xe = jnp.take(x_pad, dispatch_idx, axis=0)              # (E,C,d)
    meta = (expert_idx, gate_w, pos_c, keep)
    return xe, meta, aux


def combine_local(ye, meta, cfg):
    """Invert dispatch_local: ye (E,C,d) expert outputs -> (T,d)."""
    expert_idx, gate_w, pos_c, keep = meta
    E, C, d = ye.shape
    k = cfg.experts_per_token
    T = expert_idx.shape[0]
    ye_pad = jnp.concatenate([ye, jnp.zeros((E, 1, d), ye.dtype)], axis=1)
    flat_e = expert_idx.reshape(-1)
    out_rows = ye_pad[flat_e, pos_c]
    w = (gate_w.reshape(-1) * keep).astype(out_rows.dtype)
    return (out_rows * w[:, None]).reshape(T, k, d).sum(axis=1)
