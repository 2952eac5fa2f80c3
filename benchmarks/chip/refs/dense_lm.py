"""Plain float32 reference of a decoder-only transformer with grouped-query
attention, rotary positions (rotate-half), RMSNorm and a SwiGLU MLP: the
Qwen2 architecture (arXiv:2407.10671; Hugging Face ``Qwen2ForCausalLM``).

Written from the published description in straightforward ``jax.numpy``,
with no kernel, cache or batching, and every matrix product at
``highest`` precision.  It imports nothing of the program under test.

It also makes the weights that the benchmark hands to the program (in the
tree the program's ``api.init`` lays out, as the served dtype), so the
reference and the program read the same numbers and neither takes them
from the other.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.chip.refs_common import mm, rms, tree_init

F32 = jnp.float32


def program_options(c: dict) -> dict:
    """The program's ModelConfig fields that the program lets a user set,
    set as the configuration states them."""
    return {"tie_embeddings": c["tie_word_embeddings"]}


def program_fields(c: dict) -> dict:
    """The program's ModelConfig fields that must equal the configuration."""
    return {"num_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "d_ff": c["intermediate_size"], "vocab_size": c["vocab_size"],
            "qkv_bias": c["attention_bias"], "rope_theta": c["rope_theta"],
            "norm_eps": c["rms_norm_eps"],
            "tie_embeddings": c["tie_word_embeddings"], "dtype": c["dtype"]}


def _dims(c):
    d, H, K = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    return d, H, K, hd, c["intermediate_size"], c["num_hidden_layers"]


def padded_vocab(c: dict) -> int:
    return -(-c["vocab_size"] // 128) * 128


def param_shapes(c: dict) -> dict:
    """name -> shape of every weight, in the program's tree."""
    d, H, K, hd, f, L = _dims(c)
    V = padded_vocab(c)
    attn = {"wq": (L, d, H * hd), "wk": (L, d, K * hd), "wv": (L, d, K * hd),
            "wo": (L, H * hd, d)}
    if c["attention_bias"]:
        attn.update(bq=(L, H * hd), bk=(L, K * hd), bv=(L, K * hd))
    p = {"embed": (V, d), "ln_f": (d,),
         "layers": {"attn": attn, "ln1": (L, d), "ln2": (L, d),
                    "mlp": {"wg": (L, d, f), "wu": (L, d, f),
                            "wd": (L, f, d)}}}
    if not c["tie_word_embeddings"]:
        p["lm_head"] = (d, V)
    return p


def _leaf(key, name, shape, dt):
    """Norms start at one; the embedding and the biases have standard
    deviation 0.02, the matrices fan-in scaling."""
    if name.startswith("ln"):
        return jnp.ones(shape, dt)
    std = 0.02 if name == "embed" or name.startswith("b") \
        else 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(key, shape, F32) * std).astype(dt)


def init(key, c: dict):
    """Every weight from ``key``, in one jitted call on the default device,
    as the served dtype."""
    dt = jnp.dtype(c["dtype"])
    return tree_init(param_shapes(c),
                     lambda k, name, shape: _leaf(k, name, shape, dt))(key)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _rope(x, pos, theta):
    """x (S, heads, hd); rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[:, None].astype(F32) * inv                  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(params, c: dict, tokens, low: bool = False):
    """Final hidden states (S, d) of one sequence ``tokens`` (S,)."""
    d, H, K, hd, f, L = _dims(c)
    eps = c["rms_norm_eps"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    h = params["embed"][tokens].astype(F32)

    def layer(h, lp):
        a, m = lp["attn"], lp["mlp"]
        x = rms(h, lp["ln1"], eps)
        q, k, v = mm(x, a["wq"], low), mm(x, a["wk"], low), mm(x, a["wv"], low)
        if "bq" in a:
            q, k, v = q + a["bq"].astype(F32), k + a["bk"].astype(F32), \
                v + a["bv"].astype(F32)
        q = _rope(q.reshape(S, H, hd), pos, c["rope_theta"])
        k = _rope(k.reshape(S, K, hd), pos, c["rope_theta"])
        v = v.reshape(S, K, hd)
        k = jnp.repeat(k, H // K, axis=1)                 # head i -> kv i//G
        v = jnp.repeat(v, H // K, axis=1)
        s = jnp.einsum("shd,thd->hst", q, k) / math.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hst,thd->shd", jax.nn.softmax(s, -1), v)
        h = h + mm(o.reshape(S, H * hd), a["wo"], low)
        x = rms(h, lp["ln2"], eps)
        g = jax.nn.silu(mm(x, m["wg"], low)) * mm(x, m["wu"], low)
        return h + mm(g, m["wd"], low), None

    h, _ = jax.lax.scan(layer, h, params["layers"])
    return rms(h, params["ln_f"], eps)


def logits_at(params, c: dict, tokens, rows, low: bool = False):
    """Logits (len(rows), vocab) at positions ``rows`` of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, c, tokens, low)[rows]
        w = params["embed"].T if c["tie_word_embeddings"] else params["lm_head"]
        return mm(h, w, low)[:, :c["vocab_size"]]
