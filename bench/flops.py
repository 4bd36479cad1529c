"""Operations and bytes the benchmark's work needs, from shapes alone.

``m`` is a configuration's ``model`` block (Hugging Face key names).
``published`` is the configuration's ``published`` block: a key that the
configuration cut keeps its published value there (``n_routed_experts``
where a chip holds its share of the experts).  Counts are of useful
work: the tokens a request really holds, the logits whose argmax is
served, the corpus rows really scanned.  Padding, prefill logits that
are thrown away and recomputation never count.

Per token at position t (0-based) of a sequence, one forward pass costs

    2 * A                     weight products: A weights met per token
    + L * a * (t + 1)         attention over the causal context

and each served token adds ``2 * d * V`` for its logits.  By layer (d
the hidden size, H heads):

Attention.  Multi-head or grouped (KH key-value heads of ``head_dim``
hd, else d / H): weights ``d*H*hd + 2*d*KH*hd + H*hd*d``, and
``a = 4*H*hd`` (q.k and p.v) per cached position.  Latent (MLA, where
``kv_lora_rank`` c is given; n, r, v the nope, rope and value head
sizes): q ``d*H*(n+r)``, or ``d*r_q + r_q*H*(n+r)`` with a
``q_lora_rank`` r_q; kv_a ``d*(c+r)``; kv_b ``c*H*(n+v)``; o ``H*v*d``;
and ``a = 2*H*(n+r) + 2*H*v``, the published un-absorbed form: a
program that folds kv_b into q and o does the same useful work and is
counted alike.

FFN (SwiGLU: three matrices).  A dense layer holds ``3*d*f``.  Where
``n_routed_experts`` is given, every layer from ``first_k_dense_replace``
on is an expert layer of experts of width f_e
(``moe_intermediate_size``): a router of ``d*E_pub`` (it scores all the
published experts), ``n_shared_experts`` shared experts of ``3*d*f_e``,
and ``E_held`` routed experts of ``3*d*f_e`` held on this chip.  A token
meets the router, the shared experts and ``k * E_held / E_pub`` routed
experts (k = ``num_experts_per_tok``): the expected number of its k
experts that lie among the ``E_held`` of ``E_pub`` this chip holds, its
share under expert parallelism; the rest are other chips' work.

Weight counts (``params``, ``weight_bytes``, ``decode_bytes``,
``prefill_bytes``) are of the weights held here.  A block that carries a
mechanism this count does not model (mixed layer kinds, windowed,
linear, indexed or state-space attention, convolutions, experts under
another key) raises ``ValueError`` naming the key, so that no reading
is silently wrong.
"""

from __future__ import annotations

BF16_BYTES = 2
F32_BYTES = 4

# Keys, or parts of keys, of mechanisms the count does not model.  A key
# set to anything but null, false, 0 or empty is refused (a sliding_window
# that use_sliding_window switches off is not set).
UNMODELLED_KEYS = ("num_local_experts", "num_experts", "moe_num_experts",
                   "mixer_types", "attn_type_list", "full_attention_layers",
                   "local_layer_ids", "gqa_layers")
UNMODELLED_PARTS = ("layer_types", "block_type", "_pattern",
                    "sliding_window", "swa_", "window_size",
                    "attention_chunk", "linear_att", "linear_conv",
                    "linear_key", "linear_value", "linear_num", "mamba",
                    "ssm", "conv", "time_step", "index_", "indexer",
                    "per_layer", "_interval")


def check(m: dict) -> dict:
    """``m``, or ``ValueError`` naming a key whose mechanism is not
    counted."""
    for k, v in m.items():
        off = k == "sliding_window" and m.get("use_sliding_window") is False
        if off or v in (None, False, 0, [], {}):
            continue
        if k in UNMODELLED_KEYS or any(p in k for p in UNMODELLED_PARTS) \
                or (k == "moe_layer_freq" and v != 1):
            raise ValueError(f"flops: {k}={v!r} is a mechanism this count "
                             f"does not model")
    return m


def _published(m: dict, published, key: str):
    return (published or {}).get(key, m[key])


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def attention_params(m: dict, published=None) -> int:
    """Weights of one layer's attention."""
    d, H = check(m)["hidden_size"], m["num_attention_heads"]
    if m.get("kv_lora_rank"):
        c, n, r, v = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                      m["qk_rope_head_dim"], m["v_head_dim"])
        rq = m.get("q_lora_rank")
        q = d * rq + rq * H * (n + r) if rq else d * H * (n + r)
        return q + d * (c + r) + c * H * (n + v) + H * v * d
    KH, hd = m["num_key_value_heads"], head_dim(m)
    return d * H * hd + 2 * d * KH * hd + H * hd * d


def attention_flops_per_position(m: dict, published=None) -> int:
    """FLOPs of one layer's attention for each cached position a token
    attends to (q.k and p.v)."""
    H = check(m)["num_attention_heads"]
    if m.get("kv_lora_rank"):
        return (2 * H * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"])
                + 2 * H * m["v_head_dim"])
    return 4 * H * head_dim(m)


def _expert(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def is_expert_layer(m: dict, layer: int) -> bool:
    return bool(m.get("n_routed_experts")) and \
        layer >= m.get("first_k_dense_replace", 0)


def expert_params(m: dict) -> int:
    """Routed expert weights one expert layer holds here."""
    return m["n_routed_experts"] * _expert(m)


def layer_params(m: dict, layer: int = 0, published=None) -> int:
    """Weights layer ``layer`` holds here."""
    attn = attention_params(m, published)
    d = m["hidden_size"]
    if not is_expert_layer(m, layer):
        return attn + 3 * d * m["intermediate_size"]
    return (attn + d * _published(m, published, "n_routed_experts")
            + m.get("n_shared_experts", 0) * _expert(m) + expert_params(m))


def layer_token_params(m: dict, layer: int = 0, published=None) -> float:
    """Weights of layer ``layer`` that one token is multiplied by."""
    held = layer_params(m, layer, published)
    if not is_expert_layer(m, layer):
        return held
    routed = m["num_experts_per_tok"] * expert_params(m) \
        / _published(m, published, "n_routed_experts")
    return held - expert_params(m) + routed


def matmul_params(m: dict, published=None) -> int:
    """Weights of the layers held here (no embedding, no head)."""
    return sum(layer_params(m, i, published)
               for i in range(m["num_hidden_layers"]))


def token_params(m: dict, published=None) -> float:
    """Layer weights one token is multiplied by (no embedding, no head)."""
    return sum(layer_token_params(m, i, published)
               for i in range(m["num_hidden_layers"]))


def params(m: dict, published=None) -> int:
    """All parameters held here: layers, embedding table, untied head."""
    emb = m["vocab_size"] * m["hidden_size"]
    return matmul_params(m, published) + emb * (
        1 if m["tie_word_embeddings"] else 2)


def _per_token(m: dict, published) -> tuple:
    """(2 * weights met per token, L * attention FLOPs per position)."""
    return (2.0 * token_params(m, published),
            m["num_hidden_layers"] * attention_flops_per_position(m))


def _forward(cost: tuple, n: int) -> float:
    w, a = cost
    return w * n + a * n * (n + 1) / 2


def sequence_flops(m: dict, n: int, published=None) -> float:
    """Forward FLOPs of the n tokens of one sequence, without logits."""
    return _forward(_per_token(m, published), n)


def logits_flops(m: dict, tokens: int, published=None) -> float:
    return 2.0 * m["hidden_size"] * m["vocab_size"] * tokens


def generation_flops(m: dict, sequences, published=None) -> float:
    """FLOPs of served generation requests given as (prompt tokens,
    generated tokens) pairs: every position the model processed (the
    last generated token is returned, never fed back) and the logits of
    each served token."""
    cost = _per_token(m, published)
    total = 0.0
    for p, g in sequences:
        total += _forward(cost, p + max(g - 1, 0)) + logits_flops(m, g)
    return total


def embed_flops(m: dict, lengths, published=None) -> float:
    """FLOPs of embedding texts of the given token counts (no logits)."""
    cost = _per_token(m, published)
    return sum(_forward(cost, n) for n in lengths)


def weight_bytes(m: dict, dtype_bytes: int = BF16_BYTES,
                 published=None) -> int:
    return params(m, published) * dtype_bytes


def kv_bytes_per_token(m: dict, dtype_bytes: int = BF16_BYTES,
                       published=None) -> int:
    """Cache entries of one token over every layer: keys and values, or
    MLA's latent and its rope key."""
    L = check(m)["num_hidden_layers"]
    if m.get("kv_lora_rank"):
        return L * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * dtype_bytes
    return 2 * L * m["num_key_value_heads"] * head_dim(m) * dtype_bytes


def decode_bytes(m: dict, context_tokens: int, new_tokens: int,
                 published=None) -> int:
    """HBM bytes one decode step needs: every weight held here once, the
    cache entries of the ``context_tokens`` attended to, and the
    ``new_tokens`` entries written (one per active slot)."""
    return (weight_bytes(m, published=published)
            + kv_bytes_per_token(m) * (context_tokens + new_tokens))


def prefill_bytes(m: dict, context_tokens: int, chunk_tokens: int,
                  published=None) -> int:
    """HBM bytes one prefill chunk needs: every weight held here once,
    the cached context read, and the chunk's entries written."""
    return (weight_bytes(m, published=published)
            + kv_bytes_per_token(m) * (context_tokens + chunk_tokens))


def scan_bytes(rows: int, dim: int, queries: int) -> int:
    """One exact float32 scan: the corpus and the queries read once."""
    return (rows + queries) * dim * F32_BYTES


def scan_flops(rows: int, dim: int, queries: int) -> float:
    return 2.0 * rows * dim * queries
