"""D-mode: the discrete programming model (paper's D-MGPU) via shard_map.

Where U-mode lets GSPMD decide every collective, D-mode is the paper's
lesson applied: the *programmer* owns data placement and every byte that
crosses a device boundary is an explicit `jax.lax` collective:

* `tp_loss`          — Megatron tensor-parallel dense transformer:
                       column/row-parallel matmuls with exactly ONE psum
                       per attention block and ONE per MLP; vocab-sharded
                       logits with a distributed (psum/pmax) softmax
                       cross-entropy — logits never materialize globally.
* `ep_moe_ffn`       — expert parallelism: capacity dispatch, one
                       all_to_all out, local expert FFN, one all_to_all
                       back (the paper's Scatter/Irregular pattern).
* `sp_flash_decode`  — sequence-parallel decode: the KV cache is
                       seq-sharded over "model"; each shard computes a
                       partial (m, l, acc) online-softmax triple and the
                       exact result combines with one pmax + two psums —
                       this is how kv_heads=2 archs use a 16-wide model
                       axis that head-sharding cannot.

Differentiable end-to-end (collectives have transpose rules), so
`jax.grad` over `tp_loss` yields a D-mode train step.
"""
from __future__ import annotations

import functools
import math
import typing

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import layers as L
from repro.models import moe as M
from repro.models.base import ModelConfig


# --------------------------------------------------------------------------
# Megatron TP dense transformer (explicit collectives)
# --------------------------------------------------------------------------

def _tp_attention(lp, h, cfg, positions, axis: str):
    """Column-parallel QKV (head shards), row-parallel WO, one psum."""
    B, S, _ = h.shape
    m = jax.lax.axis_size(axis)
    Hl = cfg.num_heads // m                     # local q heads
    q = h @ lp["wq"]                            # wq: (d, q_dim/m) local
    k = h @ lp["wk"]                            # kv replicated or sharded
    v = h @ lp["wv"]
    if "bq" in lp:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(B, S, Hl, cfg.hd)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.hd)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.hd)
    ang = L.rope_angles(positions, cfg.hd, cfg.rope_theta)
    q, k = L.apply_rope(q, ang), L.apply_rope(k, ang)
    # KV is replicated across TP ranks; expand to q-head space and take
    # this rank's local heads so GQA grouping works for any Hl vs K.
    G = cfg.num_heads // cfg.num_kv_heads
    idx = jax.lax.axis_index(axis)
    k = jax.lax.dynamic_slice_in_dim(jnp.repeat(k, G, axis=2),
                                     idx * Hl, Hl, axis=2)
    v = jax.lax.dynamic_slice_in_dim(jnp.repeat(v, G, axis=2),
                                     idx * Hl, Hl, axis=2)
    o = L.attention_core(q, k, v, causal=True,
                         impl="blocked" if cfg.attn_impl != "ref" else "ref")
    o = o.reshape(B, S, Hl * cfg.hd) @ lp["wo"]  # wo: (q_dim/m, d) local
    return jax.lax.psum(o, axis)                # THE attention all-reduce


def _tp_mlp(lp, h, axis: str):
    y = (jax.nn.silu(h @ lp["wg"]) * (h @ lp["wu"])) @ lp["wd"]
    return jax.lax.psum(y, axis)                # THE mlp all-reduce


def _vocab_sharded_xent(logits_l, targets, vocab_start, axis: str):
    """Distributed cross-entropy over vocab shards: logits (B,S,V/m)."""
    logits_l = logits_l.astype(jnp.float32)
    m_local = jnp.max(jax.lax.stop_gradient(logits_l), axis=-1)
    # the shift is a stability constant: stop_gradient keeps grads exact
    # (and pmax has no transpose rule anyway)
    m_glob = jax.lax.pmax(m_local, axis)                     # (B,S)
    z = jax.lax.psum(
        jnp.sum(jnp.exp(logits_l - m_glob[..., None]), axis=-1), axis)
    Vl = logits_l.shape[-1]
    local_t = targets - vocab_start
    in_shard = (local_t >= 0) & (local_t < Vl)
    gathered = jnp.take_along_axis(
        logits_l, jnp.clip(local_t, 0, Vl - 1)[..., None], axis=-1)[..., 0]
    gold = jax.lax.psum(jnp.where(in_shard, gathered, 0.0), axis)
    return jnp.mean(jnp.log(z) + m_glob - gold)


def tp_param_specs(cfg: ModelConfig) -> dict:
    """PartitionSpecs for the D-mode local-view params (dense family)."""
    lay = {"attn": {"wq": P(None, None, "model"), "wk": P(None, None, None),
                    "wv": P(None, None, None), "wo": P(None, "model", None)},
           "mlp": {"wg": P(None, None, "model"), "wu": P(None, None, "model"),
                   "wd": P(None, "model", None)},
           "ln1": P(None, None), "ln2": P(None, None)}
    if cfg.qkv_bias:
        lay["attn"].update({"bq": P(None, "model"), "bk": P(None, None),
                            "bv": P(None, None)})
    return {"embed": P(None, None), "lm_head": P(None, "model"),
            "layers": lay, "ln_f": P(None)}


def tp_loss(cfg: ModelConfig, mesh: Mesh):
    """Returns loss_fn(params, batch) built with shard_map: DP over
    "data" (batch), TP over "model". KV is replicated across TP ranks
    (GQA kv_heads < TP size), q heads and MLP are column/row parallel."""
    assert cfg.num_heads % mesh.shape["model"] == 0, \
        f"{cfg.name}: q heads must divide the model axis for D-mode TP"

    def local_loss(p, tokens, targets):
        midx = jax.lax.axis_index("model")
        B, S = tokens.shape
        h = jnp.take(p["embed"], tokens, axis=0)
        positions = jnp.arange(S)

        def body(h, lp):
            a = _tp_attention(lp["attn"],
                              L.rms_norm(h, lp["ln1"], cfg.norm_eps), cfg,
                              positions, "model")
            h = h + a
            y = _tp_mlp(lp["mlp"], L.rms_norm(h, lp["ln2"], cfg.norm_eps),
                        "model")
            return h + y, None

        body = jax.checkpoint(body)
        h, _ = jax.lax.scan(body, h, p["layers"])
        h = L.rms_norm(h, p["ln_f"], cfg.norm_eps)
        logits_l = h @ p["lm_head"]                # (B,S,V/m) vocab shard
        Vl = logits_l.shape[-1]
        nll = _vocab_sharded_xent(logits_l, targets, midx * Vl, "model")
        return jax.lax.pmean(nll, "data")

    in_specs = (tp_param_specs(cfg), P("data", None), P("data", None))
    fn = jax.shard_map(local_loss, mesh=mesh, in_specs=in_specs,
                       out_specs=P(), check_vma=False)
    return lambda params, batch: fn(params, batch["tokens"],
                                    batch["targets"])


# --------------------------------------------------------------------------
# Expert parallelism (MoE) with explicit all_to_all
# --------------------------------------------------------------------------

def ep_moe_ffn(p, x, cfg: ModelConfig, axis: str = "model"):
    """Inside shard_map: x (T_local, d) local tokens; p holds the LOCAL
    expert slices (E_local = E/m on the expert axis) and a replicated
    router.  Two all_to_alls move each token to/from its experts."""
    m = jax.lax.axis_size(axis)
    T, d = x.shape
    E = cfg.num_experts
    El = E // m
    C = M.capacity(T, cfg)
    xe, meta, aux = M.dispatch_local({"router": p["router"]}, x, cfg, C)
    # (E, C, d) -> exchange -> (E_local, m*C, d): tokens for MY experts.
    # tiled=True keeps the op layout-symmetric so its VJP is the mirror
    # all_to_all (the untiled reshape form breaks cotangent layouts).
    xr = jax.lax.all_to_all(xe, axis, split_axis=0, concat_axis=1,
                            tiled=True)
    ye = M.expert_ffn({"wg": p["wg"], "wu": p["wu"], "wd": p["wd"]}, xr)
    # reverse exchange: (E_local, m*C, d) -> (E, C, d) back at the senders
    yb = jax.lax.all_to_all(ye, axis, split_axis=1, concat_axis=0,
                            tiled=True)
    return M.combine_local(yb, meta, cfg).astype(x.dtype), aux


# --------------------------------------------------------------------------
# Sequence-parallel decode (flash-decode combine)
# --------------------------------------------------------------------------

def sp_flash_decode_step(q, k_shard, v_shard, lengths_local, axis="model"):
    """q (B,H,hd) one token; k/v_shard (B,Tl,K,hd) this shard's KV rows;
    lengths_local (B,) = how many rows of THIS shard are valid.
    Exact softmax over the full (sharded) sequence with one pmax + two
    psums — the collective cost is O(B*H*hd), independent of seq_len."""
    B, H, hd = q.shape
    K = k_shard.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgh,btkh->bkgt", qg, k_shard.astype(jnp.float32))
    s = s / math.sqrt(hd)
    Tl = k_shard.shape[1]
    valid = jnp.arange(Tl)[None, :] < lengths_local[:, None]     # (B,Tl)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    m_loc = jnp.max(s, axis=-1)                                  # (B,K,G)
    m_glob = jax.lax.pmax(m_loc, axis)
    p = jnp.exp(s - m_glob[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l_loc = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgt,btkh->bkgh", p, v_shard.astype(jnp.float32))
    l = jax.lax.psum(l_loc, axis)
    acc = jax.lax.psum(acc, axis)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, H, hd)


def make_sp_decode_attention(mesh: Mesh, cfg: ModelConfig,
                             pos_spec: P = P()):
    """shard_map wrapper: cache seq dim sharded over "model", batch over
    "data"; returns attention(q, k_cache, v_cache, pos) -> (B,H,hd).
    Pass pos_spec=P("data") for per-slot (B,) positions."""
    def local(q, kc, vc, pos):
        m = jax.lax.axis_size("model")
        idx = jax.lax.axis_index("model")
        Tl = kc.shape[1]
        start = idx * Tl
        # rows valid on this shard: clip(pos+1 - start, 0, Tl)
        lengths = jnp.clip(pos + 1 - start, 0, Tl)
        if lengths.ndim == 0:                 # scalar pos -> per-row
            lengths = jnp.broadcast_to(lengths, (q.shape[0],))
        return sp_flash_decode_step(q, kc, vc, lengths)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("data", None, None), P("data", "model", None, None),
                  P("data", "model", None, None), pos_spec),
        out_specs=P("data", None, None), check_vma=False)
