"""jit'd wrapper: block-max prune (Pallas) + exact rescore (XLA)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import block_max_scores

F32 = jnp.float32


@partial(jax.jit,
         static_argnames=("k", "block_n", "block_t", "interpret"))
def topk_sim(corpus, queries, k: int, *, block_n: int = 64,
             block_t=None, interpret=None):
    """Exact cosine top-k via block-max pruning.

    corpus: (N, D) (normalised inside); queries: (Q, D).
    Returns (scores (Q, k), indices (Q, k)), exact (see kernel.py proof).
    ``k`` is capped at N; an empty corpus returns empty (Q, 0) results.
    ``interpret=None`` resolves per backend (interpreter on the CPU
    only)."""
    N, D = corpus.shape
    Q = queries.shape[0]
    k = min(k, N)
    if N == 0 or k == 0 or Q == 0:
        return (jnp.zeros((Q, min(k, N)), F32),
                jnp.zeros((Q, min(k, N)), jnp.int32))
    block_n = min(block_n, -(-N // 8) * 8)
    cn = corpus / jnp.maximum(
        jnp.linalg.norm(corpus, axis=-1, keepdims=True), 1e-9)
    qn = queries / jnp.maximum(
        jnp.linalg.norm(queries, axis=-1, keepdims=True), 1e-9)
    qn = qn.astype(cn.dtype)

    bmax = block_max_scores(cn, qn, block_n=block_n, block_t=block_t,
                            interpret=interpret)     # (Q, n_blocks)
    n_blocks = bmax.shape[1]
    kb = min(k, n_blocks)
    _, top_blocks = jax.lax.top_k(bmax, kb)               # (Q, kb)

    # gather candidate rows: (Q, kb*block_n, D)
    row_idx = (top_blocks[:, :, None] * block_n
               + jnp.arange(block_n)[None, None, :]).reshape(Q, kb * block_n)
    row_idx = jnp.minimum(row_idx, N - 1)
    in_range = row_idx < N
    cand = jnp.take(cn, row_idx, axis=0)                  # (Q, kb*bn, D)
    s = jnp.einsum("qd,qnd->qn", qn.astype(F32), cand.astype(F32),
                   precision=jax.lax.Precision.HIGHEST)
    s = jnp.where(in_range, s, -jnp.inf)
    # dedupe clipped duplicates (same row gathered twice scores twice —
    # mask all but the first occurrence)
    sorted_rows = jnp.sort(row_idx, axis=1)
    first = jnp.concatenate(
        [jnp.ones((Q, 1), bool),
         sorted_rows[:, 1:] != sorted_rows[:, :-1]], axis=1)
    order = jnp.argsort(row_idx, axis=1)
    inv = jnp.argsort(order, axis=1)
    keep = jnp.take_along_axis(first, inv, axis=1)
    s = jnp.where(keep, s, -jnp.inf)
    top_s, pos = jax.lax.top_k(s, k)
    top_i = jnp.take_along_axis(row_idx, pos, axis=1)
    return top_s, top_i
