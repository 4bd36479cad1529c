"""Operations and bytes the benchmark's work needs, from shapes alone.

``m`` is a configuration's ``model`` block (Hugging Face key names).
Counts are of useful work: the tokens a request really holds, the logits
whose argmax is served, the corpus rows really scanned.  Padding,
prefill logits that are thrown away and recomputation never count.

Per token at position t (0-based) of a sequence, one forward pass of a
dense transformer costs

    2 * P_layers                      weight products of every layer
    + 4 * L * H * hd * (t + 1)        q.k and p.v over the causal context

and each served token adds ``2 * d * V`` for its logits.  ``P_layers`` is
``L * (d*H*hd + 2*d*KH*hd + H*hd*d + 3*d*f)`` (SwiGLU: three matrices).
"""

from __future__ import annotations

BF16_BYTES = 2
F32_BYTES = 4


def layer_params(m: dict) -> int:
    d, H, KH = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"]
    hd, f = m["head_dim"], m["intermediate_size"]
    return d * H * hd + 2 * d * KH * hd + H * hd * d + 3 * d * f


def matmul_params(m: dict) -> int:
    """Parameters in the layers' weight products (no embedding, no head)."""
    return m["num_hidden_layers"] * layer_params(m)


def params(m: dict) -> int:
    """All parameters: layers, embedding table, untied head."""
    emb = m["vocab_size"] * m["hidden_size"]
    return matmul_params(m) + emb * (1 if m["tie_word_embeddings"] else 2)


def sequence_flops(m: dict, n: int) -> float:
    """Forward FLOPs of the n tokens of one sequence, without logits."""
    L, H, hd = m["num_hidden_layers"], m["num_attention_heads"], \
        m["head_dim"]
    return 2.0 * matmul_params(m) * n + 4.0 * L * H * hd * n * (n + 1) / 2


def logits_flops(m: dict, tokens: int) -> float:
    return 2.0 * m["hidden_size"] * m["vocab_size"] * tokens


def generation_flops(m: dict, sequences) -> float:
    """FLOPs of served generation requests given as (prompt tokens,
    generated tokens) pairs: every position the model processed (the
    last generated token is returned, never fed back) and the logits of
    each served token."""
    total = 0.0
    for p, g in sequences:
        total += sequence_flops(m, p + max(g - 1, 0)) + logits_flops(m, g)
    return total


def embed_flops(m: dict, lengths) -> float:
    """FLOPs of embedding texts of the given token counts (no logits)."""
    return sum(sequence_flops(m, n) for n in lengths)


def weight_bytes(m: dict, dtype_bytes: int = BF16_BYTES) -> int:
    return params(m) * dtype_bytes


def kv_bytes_per_token(m: dict, dtype_bytes: int = BF16_BYTES) -> int:
    """Key and value of one token over every layer."""
    return (2 * m["num_hidden_layers"] * m["num_key_value_heads"]
            * m["head_dim"] * dtype_bytes)


def decode_bytes(m: dict, context_tokens: int, new_tokens: int) -> int:
    """HBM bytes one decode step needs: every weight once, the keys and
    values of the ``context_tokens`` attended to, and the ``new_tokens``
    entries written (one per active slot)."""
    return (weight_bytes(m)
            + kv_bytes_per_token(m) * (context_tokens + new_tokens))


def prefill_bytes(m: dict, context_tokens: int, chunk_tokens: int) -> int:
    """HBM bytes one prefill chunk needs: every weight once, the cached
    context read, and the chunk's keys and values written."""
    return (weight_bytes(m)
            + kv_bytes_per_token(m) * (context_tokens + chunk_tokens))


def scan_bytes(rows: int, dim: int, queries: int) -> int:
    """One exact float32 scan: the corpus and the queries read once."""
    return (rows + queries) * dim * F32_BYTES


def scan_flops(rows: int, dim: int, queries: int) -> float:
    return 2.0 * rows * dim * queries
