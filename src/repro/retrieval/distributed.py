"""Mesh-sharded corpus scan: the hybrid-search vector index at pod scale.

The paper's Query 3 scans every passage embedding; at cluster scale the
corpus shards across the mesh.  ``make_sharded_topk(mesh)`` builds a
``shard_map``-composed scan: corpus rows shard over every mesh axis (pure
data parallelism — queries replicate), each shard runs the same two-phase
block-max prune as the ``kernels/topk_sim`` Pallas kernel (per-block
maxima -> top-k blocks -> exact rescore of only those rows, so the full
(Q, N/shard) score matrix is never materialised), and only the
(Q, devices*k) per-shard candidates all-gather for the final top-k —
collective payload is k/shard_rows of the naive approach.

The shard-local prune is plain jnp (``lax.map`` over corpus blocks) so
it lowers on every backend under ``shard_map``; the single-device path
in ``VectorIndex`` routes through the Pallas kernel itself.

``sharded_topk`` remains the GSPMD reference formulation (einsum +
top_k, partitioned from in-shardings alone); the bound fast path is
``make_sharded_topk``, which tests/test_distributed.py lowers on an
8-device mesh and checks for both oracle numerics and a compiled HLO
that keeps the corpus sharded (no full all-gather of it).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _local_topk(corpus_rows, queries, k: int, row_offset):
    """Exact top-k of ``queries`` against a contiguous corpus slice."""
    s = jnp.einsum("qd,nd->qn", queries, corpus_rows, precision=HIGHEST,
                   preferred_element_type=F32)
    top_s, top_i = jax.lax.top_k(s, k)
    return top_s, top_i + row_offset


def sharded_topk(corpus, queries, k: int):
    """corpus: (N, D) [shard rows over the mesh]; queries: (Q, D)
    [replicated].  Returns (scores (Q, k), indices (Q, k)).

    GSPMD reference: written so the partitioner splits it from the
    in-shardings alone — the einsum + top_k run shard-local, then one
    small all-gather + final top_k reduce the candidates.
    """
    N = corpus.shape[0]
    qn = queries / jnp.maximum(
        jnp.linalg.norm(queries, axis=-1, keepdims=True), 1e-9)
    cn = corpus / jnp.maximum(
        jnp.linalg.norm(corpus, axis=-1, keepdims=True), 1e-9)
    k = min(k, N)
    s = jnp.einsum("qd,nd->qn", qn.astype(F32), cn.astype(F32),
                   precision=HIGHEST)
    top_s, top_i = jax.lax.top_k(s, k)
    return top_s, top_i


def _blocked_local_topk(c, qn, k: int, offset, n_global: int, block: int):
    """Shard-local exact top-k with the ``topk_sim`` block-max structure,
    in plain jnp: per-block maxima via a sequential on-device ``lax.map``
    (live memory (Q, n_blocks), never (Q, rows)), top-k blocks, exact
    rescore of the gathered candidates.  ``offset`` is this shard's
    global row offset; rows at global id >= ``n_global`` are padding."""
    rows, D = c.shape
    Q = qn.shape[0]
    bn = min(block, rows)
    nb = -(-rows // bn)
    pad = nb * bn - rows
    cp = jnp.pad(c, ((0, pad), (0, 0))) if pad else c
    gids = offset + jnp.arange(nb * bn)
    valid = gids < n_global

    def bmax(blk):
        cb, vb = blk                                  # (bn, D), (bn,)
        s = jnp.einsum("qd,nd->qn", qn, cb, precision=HIGHEST,
                       preferred_element_type=F32)
        return jnp.where(vb[None, :], s, -jnp.inf).max(axis=1)

    bm = jax.lax.map(bmax, (cp.reshape(nb, bn, D),
                            valid.reshape(nb, bn)))   # (nb, Q)
    kb = min(k, nb)
    _, top_blocks = jax.lax.top_k(bm.T, kb)           # (Q, kb)
    row_idx = (top_blocks[:, :, None] * bn
               + jnp.arange(bn)[None, None, :]).reshape(Q, kb * bn)
    cand = jnp.take(cp, row_idx, axis=0)              # (Q, kb*bn, D)
    s = jnp.einsum("qd,qnd->qn", qn, cand, precision=HIGHEST,
                   preferred_element_type=F32)
    s = jnp.where(valid[row_idx], s, -jnp.inf)
    top_s, pos = jax.lax.top_k(s, k)
    return top_s, jnp.take_along_axis(gids[row_idx], pos, axis=1)


def _flat_axes(mesh: Mesh, corpus_axes) -> tuple:
    axes = corpus_axes or tuple(mesh.axis_names)
    if isinstance(axes, str):
        axes = (axes,)
    flat = []
    for a in axes:
        flat.extend(a if isinstance(a, (tuple, list)) else (a,))
    return tuple(flat)


def make_sharded_topk(mesh: Mesh, k: int, *, corpus_axes=None,
                      block: int = 2048, n_valid=None):
    """Bind the shard-mapped blocked scan: corpus rows over every mesh
    axis, queries replicated, (Q, shards*k) candidate all-gather only.
    ``n_valid`` rows of the corpus are real (default: all of them); rows
    past it are padding that a caller placed to even out the shards."""
    axes = _flat_axes(mesh, corpus_axes)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    nshards = math.prod(sizes[a] for a in axes)

    def fn(corpus, queries):
        N = corpus.shape[0] if n_valid is None else n_valid
        cn = corpus / jnp.maximum(
            jnp.linalg.norm(corpus, axis=-1, keepdims=True), 1e-9)
        qn = queries / jnp.maximum(
            jnp.linalg.norm(queries, axis=-1, keepdims=True), 1e-9)
        qn = qn.astype(F32)
        kk = min(k, N)
        pad = (-cn.shape[0]) % nshards
        cp = jnp.pad(cn, ((0, pad), (0, 0))) if pad else cn
        rows_local = cp.shape[0] // nshards
        kl = min(kk, rows_local)

        def local(c, q):
            shard = 0
            for name in axes:
                shard = shard * sizes[name] + jax.lax.axis_index(name)
            return _blocked_local_topk(c, q, kl, shard * rows_local, N,
                                       block)

        cand_s, cand_i = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axes, None), P(None, None)),
            out_specs=(P(None, axes), P(None, axes)))(cp, qn)
        top_s, pos = jax.lax.top_k(cand_s, kk)     # (Q, shards*kl) -> kk
        return top_s, jnp.take_along_axis(cand_i, pos, axis=1)

    return jax.jit(
        fn,
        in_shardings=(NamedSharding(mesh, P(axes, None)),
                      NamedSharding(mesh, P(None, None))),
        out_shardings=(NamedSharding(mesh, P(None, None)),
                       NamedSharding(mesh, P(None, None))),
    )
