"""Shared neural building blocks (pure JAX, no framework deps).

Everything here is functional: params are plain dicts of jnp arrays, all
modules are `init_*(rng, cfg) -> params` + `apply(params, x, ...) -> y`.
Per-layer params are created **stacked** on a leading layer axis so the
model forward can `lax.scan` over layers (small HLO, fast compile, remat-
friendly — the MaxText idiom).

The attention core has two implementations selected by
``cfg.attn_impl``: "ref" (einsum softmax — what the dry-run lowers; also
the oracle) and "flash" (Pallas TPU kernel from ``repro.kernels``,
validated in interpret mode on CPU).

Each layer kind runs in a ``jax.named_scope`` (``attention`` with its cache
write as ``kv_update``, ``mlp``, ``norm``, ``embed``, ``unembed``), so the
compiled program's ``op_name`` metadata says which layer an operation
belongs to.
"""
from __future__ import annotations

import math
import typing

import jax
import jax.numpy as jnp

Params = typing.Dict[str, typing.Any]


# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------

def dense_init(rng, shape, dtype, scale: float = None):
    """Truncated-normal fan-in init (stacked shapes init per-slice)."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(rng, -2, 2, shape, jnp.float32)
            * scale).astype(dtype)


def embed_init(rng, shape, dtype):
    return (jax.random.normal(rng, shape, jnp.float32) * 0.02).astype(dtype)


def split_rngs(rng, n):
    return list(jax.random.split(rng, n))


# --------------------------------------------------------------------------
# normalisation
# --------------------------------------------------------------------------

@jax.named_scope("norm")
def rms_norm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------
# rotary position embedding (rotate-half convention)
# --------------------------------------------------------------------------

def rope_angles(positions, head_dim: int, theta: float):
    """positions: (...,S) int -> (...,S, head_dim//2) angles."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    return positions[..., None].astype(jnp.float32) * inv_freq


def apply_rope(x, angles):
    """x: (B,S,H,hd); angles: (S,hd/2) or (B,S,hd/2)."""
    if angles.ndim == 2:
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(seq_len: int, d_model: int, dtype):
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, dim / d_model)
    pe = jnp.zeros((seq_len, d_model), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(angle)).at[:, 1::2].set(jnp.cos(angle))
    return pe.astype(dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

def init_attention(rng, cfg, cross: bool = False) -> Params:
    """Weights for one (stacked: leading dim = n_layers) attention block."""
    d, dt = cfg.d_model, cfg.jnp_dtype
    rs = split_rngs(rng, 4)
    p = {
        "wq": dense_init(rs[0], (d, cfg.q_dim), dt),
        "wk": dense_init(rs[1], (d, cfg.kv_dim), dt),
        "wv": dense_init(rs[2], (d, cfg.kv_dim), dt),
        "wo": dense_init(rs[3], (cfg.q_dim, d), dt,
                         scale=1.0 / math.sqrt(cfg.q_dim)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dt)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dt)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dt)
    return p


def _stack_init(fn, rng, n_layers, *args, **kw):
    """Init `n_layers` instances and stack each leaf on axis 0."""
    outs = [fn(r, *args, **kw) for r in split_rngs(rng, n_layers)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)


def qkv(p: Params, x, cfg, positions=None):
    """Project + (optionally) rope. x: (B,S,d) -> q (B,S,H,hd), k/v (B,S,K,hd)."""
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.num_heads, cfg.hd)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.hd)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.hd)
    if positions is not None:
        ang = rope_angles(positions, cfg.hd, cfg.rope_theta)
        q = apply_rope(q, ang)
        k = apply_rope(k, ang)
    return q, k, v


def blocked_attention(q, k, v, causal: bool = True, q_chunk: int = 256):
    """Memory-bounded causal GQA attention: q is processed in chunks so
    only a (cq, T) score tile is ever live — the pure-JAX mirror of the
    Pallas flash kernel (kernels/flash_attention.py) that the CPU dry-run
    can lower.  Exact softmax per chunk (full kv row), f32 accumulation.

    The chunk body is itself jax.checkpoint'ed: under the per-layer remat
    the backward pass would otherwise stack every chunk's (cq, T) softmax
    probabilities and causal mask (the dominant temp buffer at S >= 4k) —
    rematerializing them per chunk trades ~30% extra attention FLOPs in
    the backward for an O(S^2) -> O(cq*T) live-memory drop.
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    cq = min(q_chunk, S)
    pad = (-S) % cq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = (S + pad) // cq
    scale = 1.0 / math.sqrt(hd)
    qs = jnp.moveaxis(q.reshape(B, nq, cq, K, G, hd), 1, 0)

    @jax.checkpoint
    def chunk(args):
        i, qi = args                                  # qi (B,cq,K,G,hd)
        s = jnp.einsum("bskgh,btkh->bkgst", qi, k,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * cq + jnp.arange(cq)[:, None] + (T - S)
            s = jnp.where(qpos >= jnp.arange(T)[None, :], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgst,btkh->bskgh", w.astype(v.dtype), v)
        return o

    out = jax.lax.map(chunk, (jnp.arange(nq), qs))    # (nq,B,cq,K,G,hd)
    out = jnp.moveaxis(out, 0, 1).reshape(B, S + pad, H, hd)
    return out[:, :S]


def attention_core(q, k, v, mask=None, causal: bool = False,
                   impl: str = "ref"):
    """GQA attention. q: (B,S,H,hd); k/v: (B,T,K,hd); H % K == 0.

    mask: broadcastable to (B,1,1,S,T) boolean (True = attend) or None.
    impl: "ref" (materialized scores), "blocked" (q-chunked, memory-safe),
    "flash" (Pallas TPU kernel).
    """
    if impl == "flash" and mask is None and causal:
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=True)
    if impl == "blocked" and mask is None and causal and q.shape[1] > 1:
        return blocked_attention(q, k, v, causal=True)
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32) * scale
    if causal:
        cm = jnp.tril(jnp.ones((S, T), bool), k=T - S)
        scores = jnp.where(cm, scores, -jnp.inf)
    if mask is not None:
        scores = jnp.where(jnp.moveaxis(mask, -2, -2), scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", w.astype(v.dtype), v)
    return out.reshape(B, S, H, hd)


@jax.named_scope("attention")
def attention_block(p: Params, x, cfg, positions=None, causal=True,
                    kv_cache=None, cache_pos=None, kv_override=None):
    """Full attention block: qkv -> core -> output proj.

    Train / prefill: kv_cache None -> self attention over x.
    Decode: kv_cache = (k_cache, v_cache) of static length T; the new
    token's k/v are written at ``cache_pos`` and attention masks t <= pos.
    Cross-attention: kv_override = (k, v) precomputed from encoder.
    Returns (out, new_kv) where new_kv is the updated (k, v) or None.
    """
    B, S, _ = x.shape
    if kv_override is not None:
        q = (x @ p["wq"] + (p.get("bq", 0)
                            )).reshape(B, S, cfg.num_heads, cfg.hd)
        if positions is not None:
            q = apply_rope(q, rope_angles(positions, cfg.hd, cfg.rope_theta))
        k, v = kv_override
        out = attention_core(q, k, v, causal=False, impl=cfg.attn_impl)
        return out.reshape(B, S, -1) @ p["wo"], None

    q, k, v = qkv(p, x, cfg, positions)
    if kv_cache is None:
        out = attention_core(q, k, v, causal=causal, impl=cfg.attn_impl)
        return out.reshape(B, S, -1) @ p["wo"], (k, v)

    kc, vc = kv_cache                       # (B, T, K, hd) static T
    T = kc.shape[1]
    cache_pos = jnp.asarray(cache_pos)
    if cache_pos.ndim == 0:
        with jax.named_scope("kv_update"):
            kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                              (0, cache_pos, 0, 0))
            vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                              (0, cache_pos, 0, 0))
        valid = (jnp.arange(T) <= cache_pos + S - 1
                 )[None, None, None, None, :]
    else:
        # per-slot positions (continuous batching): vmap the row update
        upd = jax.vmap(lambda c, n, p: jax.lax.dynamic_update_slice(
            c, n, (p, 0, 0)))
        with jax.named_scope("kv_update"):
            kc = upd(kc, k.astype(kc.dtype), cache_pos)
            vc = upd(vc, v.astype(vc.dtype), cache_pos)
        valid = (jnp.arange(T)[None, :] <= (cache_pos[:, None] + S - 1)
                 )[:, None, None, None, :]
    out = attention_core(q, kc, vc, mask=valid, impl="ref")
    return out.reshape(B, S, -1) @ p["wo"], (kc, vc)


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def init_swiglu(rng, cfg) -> Params:
    d, f, dt = cfg.d_model, cfg.d_ff, cfg.jnp_dtype
    rs = split_rngs(rng, 3)
    return {"wg": dense_init(rs[0], (d, f), dt),
            "wu": dense_init(rs[1], (d, f), dt),
            "wd": dense_init(rs[2], (f, d), dt)}


@jax.named_scope("mlp")
def swiglu(p: Params, x):
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]


def init_gelu_mlp(rng, cfg) -> Params:
    d, f, dt = cfg.d_model, cfg.d_ff, cfg.jnp_dtype
    rs = split_rngs(rng, 2)
    return {"w1": dense_init(rs[0], (d, f), dt),
            "w2": dense_init(rs[1], (f, d), dt)}


@jax.named_scope("mlp")
def gelu_mlp(p: Params, x):
    return jax.nn.gelu(x @ p["w1"]) @ p["w2"]


# --------------------------------------------------------------------------
# embedding / unembedding
# --------------------------------------------------------------------------

def init_embed(rng, cfg) -> Params:
    dt = cfg.jnp_dtype
    rs = split_rngs(rng, 2)
    V = cfg.padded_vocab
    p = {"embed": embed_init(rs[0], (V, cfg.d_model), dt)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(rs[1], (cfg.d_model, V), dt)
    return p


@jax.named_scope("embed")
def embed(p: Params, tokens):
    return jnp.take(p["embed"], tokens, axis=0)


@jax.named_scope("unembed")
def unembed(p: Params, h, cfg):
    """Project to (padded) vocab logits; padded columns masked to -inf so
    softmax/argmax semantics are exactly the unpadded model's."""
    w = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    logits = (h @ w).astype(jnp.float32)
    Vp = logits.shape[-1]
    if Vp != cfg.vocab_size:
        logits = jnp.where(jnp.arange(Vp) < cfg.vocab_size, logits, -1e30)
    return logits


# --------------------------------------------------------------------------
# loss
# --------------------------------------------------------------------------

def cross_entropy(logits, targets, mask=None):
    """logits (B,S,V) f32, targets (B,S) int32 -> scalar mean nll."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.mean(nll)
