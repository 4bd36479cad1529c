"""Fused corpus-scan top-k similarity Pallas TPU kernel (hybrid search).

The paper's Query 3 step 2 scans every passage embedding against the query
and keeps the top 100 — FlockMTL leans on DuckDB's VSS extension; here the
scan is the TPU hot spot.  Materialising the (N, Q) score matrix in HBM is
the naive cost; the kernel instead:

  phase 1 (Pallas): blocked corpus x query matmul on the MXU, emitting only
     the per-block, per-query max — (n_blocks, Q) instead of (N, Q);
  phase 2 (XLA, ops.py): select the top-k *blocks* per query (their maxes
     upper-bound every member, so the true top-k elements provably live in
     the top-k blocks), gather those k*block rows, rescore exactly, top-k.

HBM traffic: one streaming pass over the corpus + k*block_n rescore reads,
vs 1 pass + (N, Q) writes + (N, Q) reads for the naive scan.

Layout (what Mosaic accepts at real widths): scores are computed
corpus-major, ``(chunk, 128)`` with corpus rows on sublanes and a tile of
128 queries on lanes.  Each grid step takes ``block_t`` sub-blocks of
``block_n`` rows (``block_n`` a multiple of 8), so the per-sub-block max is
a reshape that splits only the sublane dimension into whole (8, 128) tiles
plus a max over it; no lane is ever split.  The output tile is
``(block_t, 128)``: ``block_t`` a multiple of 8, a full 128-lane width.
``block_t`` defaults to a corpus slab of about 4 MiB per grid step
(512 rows at D=2048 in float32), which leaves room in the default scoped
VMEM for double buffering.  Both matmuls run at float32 contract precision,
so the block maxima bound the exact rescore of phase 2.

``interpret=None`` resolves per backend (``repro.kernels``): the
interpreter on the CPU, the compiled kernel elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

F32 = jnp.float32
LANES = 128
SLAB_BYTES = 4 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _blockmax_kernel(c_ref, q_ref, o_ref, *, n_valid: int, block_n: int,
                     block_t: int):
    ci = pl.program_id(0)
    c = c_ref[...]                                   # (chunk, D)
    q = q_ref[...]                                   # (128, D)
    s = jax.lax.dot_general(c, q, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=F32)  # (chunk, 128)
    row = (ci * block_t * block_n
           + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
    s = jnp.where(row < n_valid, s, -jnp.inf)
    o_ref[...] = s.reshape(block_t, block_n, LANES).max(axis=1)


def block_max_scores(corpus, queries, *, block_n: int = 64,
                     block_t=None, interpret=None):
    """corpus: (N, D); queries: (Q, D) -> (Q, n_blocks) per-block maxima
    over sub-blocks of ``block_n`` rows (padded blocks report -inf).
    ``block_n`` must be a multiple of 8."""
    interpret = resolve_interpret(interpret)
    N, D = corpus.shape
    Q = queries.shape[0]
    if block_n % 8:
        raise ValueError(f"block_n={block_n} is not a multiple of 8")
    if block_t is None:
        block_t = max(8, SLAB_BYTES // (D * corpus.dtype.itemsize * block_n))
    n_sub = -(-N // block_n)
    block_t = min(_round_up(block_t, 8), _round_up(n_sub, 8))
    chunk = block_n * block_t
    pad = (-N) % chunk
    if pad:
        corpus = jnp.pad(corpus, ((0, pad), (0, 0)))
    qpad = (-Q) % LANES
    qp = jnp.pad(queries, ((0, qpad), (0, 0))) if qpad else queries
    grid = (corpus.shape[0] // chunk, qp.shape[0] // LANES)
    kernel = functools.partial(_blockmax_kernel, n_valid=N,
                               block_n=block_n, block_t=block_t)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, D), lambda i, j: (i, 0)),
            pl.BlockSpec((LANES, D), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, LANES), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((grid[0] * block_t, qp.shape[0]),
                                       F32),
        interpret=interpret,
    )(corpus, qp)
    return out[:, :Q].T
