"""Operations and bytes of qwen2-1.5b, counted from the shapes in
``configs/qwen2-1.5b.json`` by the algorithm's needs, not by what an
implementation happens to do.  A multiply-add counts as 2 operations;
norms, rotary embedding, softmax and other element-wise work are left out.

Attention counts only the keys a query may see: position i of a causal
sequence attends i + 1 keys, a decode token at position p attends p + 1.
"""
from __future__ import annotations


def _dims(c):
    d, H, K = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    return d, H, K, hd, c["intermediate_size"], c["num_hidden_layers"]


def layer_matmul_params(c: dict) -> int:
    """Weights of one layer that multiply every token."""
    d, H, K, hd, f, _ = _dims(c)
    return d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * f


def head_flops(c: dict) -> int:
    """Logits of one position."""
    return 2 * c["hidden_size"] * c["vocab_size"]


def attn_pair_flops(c: dict) -> int:
    """One query against one key in every layer: q.k and p*v."""
    d, H, K, hd, f, L = _dims(c)
    return 4 * H * hd * L


def prefill_flops(c: dict, S: int) -> int:
    """A prompt of S tokens, with logits at its last position only."""
    L = c["num_hidden_layers"]
    return (2 * layer_matmul_params(c) * L * S
            + attn_pair_flops(c) * S * (S + 1) // 2 + head_flops(c))


def decode_flops(c: dict, pos: int) -> int:
    """One token at position ``pos`` (it sees pos + 1 keys), with logits."""
    L = c["num_hidden_layers"]
    return (2 * layer_matmul_params(c) * L + attn_pair_flops(c) * (pos + 1)
            + head_flops(c))


def weight_bytes(c: dict, itemsize: int = 2) -> int:
    d, V, L = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    heads = 1 if c["tie_word_embeddings"] else 2
    return itemsize * (layer_matmul_params(c) * L + heads * V * d)


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    d, H, K, hd, f, L = _dims(c)
    return itemsize * 2 * L * K * hd


def decode_step_bytes(c: dict, positions) -> int:
    """Least bytes one batched decode step reads: the weights once (less
    the embedding table, of which it reads a row per token) and the keys
    and values each slot's token may see."""
    d, V = c["hidden_size"], c["vocab_size"]
    emb = 0 if c["tie_word_embeddings"] else 2 * V * d
    return (weight_bytes(c) - emb
            + sum(kv_bytes_per_token(c) * (p + 1) for p in positions))
