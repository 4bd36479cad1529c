"""Exact vector similarity search (paper Query 3 step 2 — the VSS scan).

``cosine_topk`` is the jnp oracle for the ``topk_sim`` Pallas kernel: the
corpus-side scan is a blocked matmul with a running top-k, sharded over the
(data, model) mesh when a policy is supplied.

``VectorIndex`` is the materialised index behind the ``vector_topk`` /
``hybrid_topk`` plan operators (``engine.retrieval_ops``).  The
normalised corpus is placed on the device once and kept there.  Scan
routing:

  * >1-device mesh (``mesh=`` or an enclosing ``jax.set_mesh``) — the
    shard-mapped ``distributed.make_sharded_topk`` blocked scan; corpus
    rows shard over every mesh axis, each shard copied from the host
    straight to its own device; queries replicate, and only
    (Q, devices*k) candidates all-gather.
  * single device, backend not the CPU — the ``kernels/topk_sim``
    block-max Pallas kernel, compiled.
  * CPU — the same kernel in interpret mode for corpora of at least
    ``KERNEL_MIN_ROWS_CPU`` rows, the ``cosine_topk`` jnp scan below that.

Every scan contracts at float32 precision (``Precision.HIGHEST``): the
exact scan stays exact on the TPU, whose default matmul precision rounds
float32 operands to bfloat16.

``topk_ann`` routes through a lazily built ``retrieval.ivf.IVFIndex``
(the ``vector_topk(ann=...)`` plan option); ``nprobe >= nlist`` probes
everything and reproduces the exact scan.

Built indexes are memoised per session and in the persistent
``IndexStore`` sidecar via ``ensure_index``, keyed by (embedding model
ref, corpus fingerprint).  A corpus that *extends* a memoised one is an
incremental append: only the delta is embedded (through the same
``plan_batches``/co-pack path as any embed) and stored as a new segment
next to the base instead of re-embedding the whole corpus.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .ivf import IVFIndex

# On the CPU the Pallas kernel runs interpreted; its per-call overhead
# only amortises over big corpora, so small scans keep the jnp path (which
# is also what the equivalence tests pin bit-for-bit on the CPU).
KERNEL_MIN_ROWS_CPU = 32768
DEFAULT_RECALL_TARGET = 0.95


def cosine_topk(corpus: jnp.ndarray, queries: jnp.ndarray, k: int,
                block: int = 4096):
    """corpus: (N, D) unit-normalised; queries: (Q, D).  Returns
    (scores (Q,k), indices (Q,k)) by cosine similarity, blocked over N so the
    full (N, Q) score matrix is never materialised.  ``k`` is capped at N;
    an empty corpus returns empty (Q, 0) results."""
    N, D = corpus.shape
    Q = queries.shape[0]
    k = min(k, N)
    if N == 0 or k == 0:
        return (jnp.zeros((Q, 0), jnp.float32),
                jnp.zeros((Q, 0), jnp.int32))
    qn = queries / jnp.maximum(
        jnp.linalg.norm(queries, axis=-1, keepdims=True), 1e-9)
    block = min(block, N)
    nblk = -(-N // block)
    pad = nblk * block - N
    c = jnp.pad(corpus, ((0, pad), (0, 0))) if pad else corpus
    c = c.reshape(nblk, block, D)

    def step(carry, inp):
        best_s, best_i = carry                       # (Q, k)
        blk_idx, cb = inp
        s = jnp.einsum("qd,nd->qn", qn, cb,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        idx = blk_idx * block + jnp.arange(block)
        s = jnp.where(idx[None, :] < N, s, -jnp.inf)
        cat_s = jnp.concatenate([best_s, s], axis=1)
        cat_i = jnp.concatenate([best_i,
                                 jnp.broadcast_to(idx, (Q, block))], axis=1)
        top_s, top_pos = jax.lax.top_k(cat_s, k)
        top_i = jnp.take_along_axis(cat_i, top_pos, axis=1)
        return (top_s, top_i), None

    init = (jnp.full((Q, k), -jnp.inf, jnp.float32),
            jnp.zeros((Q, k), jnp.int32))
    (s, i), _ = jax.lax.scan(step, init, (jnp.arange(nblk), c))
    return s, i


def active_mesh():
    """The mesh of an enclosing ``jax.set_mesh`` block, or None.

    A single-device mesh is reported as None — sharding the corpus over
    one device only adds dispatch overhead."""
    mesh = jax.sharding.get_mesh()
    if mesh.empty or mesh.size <= 1:
        return None
    return mesh


class VectorIndex:
    """Materialised embedding index over a column of texts.

    ``topk`` is the exact scan (mesh-sharded / Pallas / jnp — see module
    docstring); ``topk_ann`` the IVF approximate scan.  ``raw`` keeps the
    pre-normalisation vectors so segment appends (``extended``) rebuild
    bit-identically to a from-scratch index over the full corpus."""

    def __init__(self, vectors: np.ndarray, mesh=None,
                 use_kernel: Optional[bool] = None):
        v = np.asarray(vectors, np.float32)
        if v.ndim == 1:
            v = v.reshape(0, 0) if v.size == 0 else v.reshape(1, -1)
        self.raw = v
        norms = np.maximum(np.linalg.norm(v, axis=-1, keepdims=True), 1e-9)
        self.vectors = v / norms
        self.mesh = mesh
        self.use_kernel = use_kernel
        self._topk = jax.jit(cosine_topk, static_argnames=("k", "block"))
        self._sharded = {}          # (mesh, k) -> bound sharded scan
        self._placed = None         # (mesh or None, device array)
        self._ivf: Optional[IVFIndex] = None

    @classmethod
    def build(cls, ctx, model_spec, texts: Sequence[str],
              mesh=None) -> "VectorIndex":
        from repro.core.functions import llm_embedding
        return cls(llm_embedding(ctx, model_spec, list(texts)), mesh=mesh)

    def _sharded_topk(self, mesh, k: int):
        from .distributed import make_sharded_topk
        key = (id(mesh), k)
        fn = self._sharded.get(key)
        if fn is None:
            fn = self._sharded[key] = make_sharded_topk(
                mesh, k, n_valid=len(self.vectors))
        return fn

    def device_corpus(self, mesh=None) -> jax.Array:
        """The normalised corpus on the device, placed on first use and
        kept.  With a mesh, rows shard over every mesh axis and each
        shard is copied from host memory straight to its own device (no
        device ever stages the whole corpus); the row count pads with
        zero rows to a multiple of the shard count, and the sharded scan
        masks them out."""
        if self._placed is not None and self._placed[0] is mesh:
            return self._placed[1]
        self._placed = None           # drop a placement on another mesh
        if mesh is None:
            arr = jax.device_put(self.vectors)
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P
            n, d = self.vectors.shape
            n_pad = -(-n // mesh.size) * mesh.size

            def shard(idx):
                lo, hi, _ = idx[0].indices(n_pad)
                out = np.zeros((hi - lo, d), np.float32)
                out[:max(0, min(hi, n) - lo)] = self.vectors[lo:hi]
                return out

            arr = jax.make_array_from_callback(
                (n_pad, d), NamedSharding(mesh, P(mesh.axis_names, None)),
                shard)
        self._placed = (mesh, arr)
        return arr

    def _route_kernel(self) -> bool:
        if self.use_kernel is not None:
            return self.use_kernel
        if jax.default_backend() != "cpu":
            return True
        return len(self.vectors) >= KERNEL_MIN_ROWS_CPU

    def topk(self, query_vecs: np.ndarray, k: int = 100):
        q = np.atleast_2d(np.asarray(query_vecs, np.float32))
        use_k = min(k, len(self.vectors))
        if use_k <= 0 or q.shape[-1] == 0:
            return (np.zeros((len(q), 0), np.float32),
                    np.zeros((len(q), 0), np.int32))
        mesh = self.mesh if self.mesh is not None else active_mesh()
        if mesh is not None:
            fn = self._sharded_topk(mesh, use_k)
            s, i = fn(self.device_corpus(mesh), jnp.asarray(q))
        elif self._route_kernel():
            from repro.kernels.topk_sim.ops import topk_sim
            s, i = topk_sim(self.device_corpus(), jnp.asarray(q), use_k)
        else:
            s, i = self._topk(self.device_corpus(), jnp.asarray(q), use_k)
        return np.asarray(s), np.asarray(i)

    # ---- ANN -------------------------------------------------------------
    def ivf(self, nlist: Optional[int] = None) -> IVFIndex:
        """The lazily built (and memoised) IVF index over this corpus.
        An explicit ``nlist`` differing from the memoised quantizer
        rebuilds it."""
        if self._ivf is None or (
                nlist is not None and self._ivf.nlist != min(
                    max(int(nlist), 1), len(self.vectors))):
            self._ivf = IVFIndex.build(self.vectors, nlist)
        return self._ivf

    def topk_ann(self, query_vecs: np.ndarray, k: int = 100, *,
                 nprobe: Optional[int] = None,
                 nlist: Optional[int] = None,
                 recall_target: Optional[float] = None):
        """IVF approximate top-k.  ``nprobe`` wins over ``recall_target``
        (which picks the smallest calibrated nprobe meeting the target);
        ``nprobe >= nlist`` reproduces the exact scan."""
        q = np.atleast_2d(np.asarray(query_vecs, np.float32))
        use_k = min(k, len(self.vectors))
        if use_k <= 0 or q.shape[-1] == 0:
            return (np.zeros((len(q), 0), np.float32),
                    np.zeros((len(q), 0), np.int64))
        qn = q / np.maximum(np.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
        ivf = self.ivf(nlist)
        if nprobe is None:
            nprobe = ivf.nprobe_for(recall_target
                                    if recall_target is not None
                                    else DEFAULT_RECALL_TARGET)
        return ivf.search(qn, use_k, nprobe)

    # ---- incremental appends ---------------------------------------------
    def extended(self, delta_vectors: np.ndarray) -> "VectorIndex":
        """A NEW index over this corpus plus ``delta_vectors`` (raw,
        un-normalised — same as ``llm_embedding`` output).  The base
        index is untouched (it stays registered under its own
        fingerprint); a built IVF quantizer carries over with the new
        rows assigned to existing lists (merged lazily)."""
        delta = np.asarray(delta_vectors, np.float32)
        if delta.ndim == 1 and delta.size:
            delta = delta.reshape(1, -1)
        if not delta.size:
            return self
        idx = VectorIndex(np.concatenate([self.raw, delta]),
                          mesh=self.mesh, use_kernel=self.use_kernel)
        if self._ivf is not None:
            idx._ivf = self._ivf.extended(idx.vectors, len(delta))
        return idx


def _find_prefix_base(ctx, store, model_ref: str, texts):
    """An existing index over a strict prefix of ``texts``: returns
    ``(n_base, base_fp, base_index_or_None, base_vectors_or_None)`` for
    the LONGEST matching prefix, or None.  Candidates come from the
    session registry and the ``IndexStore``; a candidate of length n
    matches iff ``corpus_fingerprint(texts[:n])`` equals its key."""
    from repro.core.cache import corpus_fingerprint

    lengths = {}                       # n -> [fp, ...] candidates
    for fp, n in getattr(ctx, "index_entries", lambda _ref: [])(model_ref):
        if 0 < n < len(texts):
            lengths.setdefault(n, []).append(fp)
    if store is not None:
        for fp, n in store.entries(model_ref):
            if 0 < n < len(texts):
                lengths.setdefault(n, []).append(fp)
    for n in sorted(lengths, reverse=True):
        fp_n = corpus_fingerprint(texts[:n])
        if fp_n not in lengths[n]:
            continue
        index = ctx.lookup_index((model_ref, fp_n))
        if index is not None and len(index.vectors) == n:
            return n, fp_n, index, None
        if store is not None:
            vectors = store.get(model_ref, fp_n)
            if vectors is not None and len(vectors) == n:
                return n, fp_n, None, vectors
    return None


def ensure_index(ctx, model_spec, texts: Sequence[str],
                 fingerprint: Optional[str] = None):
    """Build-or-fetch the vector index for (embedding model, corpus).

    Lookup order: the context's session registry, then the persistent
    ``IndexStore`` sidecar, then — new in the segment era — a memoised
    index over a strict PREFIX of this corpus, in which case only the
    delta texts are embedded (the same ``plan_batches``/co-pack path as
    a full build) and persisted as an appended segment.  Returns
    ``(index, source)`` with source one of ``"session"`` / ``"store"`` /
    ``"appended"`` / ``"built"``."""
    from repro.core.cache import corpus_fingerprint
    from repro.core.functions import llm_embedding

    if not isinstance(texts, (list, tuple)):
        texts = list(texts)
    model = ctx.resolve_model(model_spec)
    if fingerprint is None:
        fingerprint = corpus_fingerprint(texts)
    key = (model.ref, fingerprint)
    index = ctx.lookup_index(key)
    if index is not None:
        return index, "session"
    store = getattr(ctx, "index_store", None)
    if store is not None:
        vectors = store.get(model.ref, fingerprint)
        if vectors is not None and len(vectors) == len(texts):
            index = VectorIndex(vectors)
            ctx.store_index(key, index)
            return index, "store"

    base = _find_prefix_base(ctx, store, model.ref, texts)
    if base is not None:
        n_base, base_fp, base_index, base_vectors = base
        delta = llm_embedding(ctx, model_spec, texts[n_base:])
        if base_index is None:
            base_index = VectorIndex(base_vectors)
        index = base_index.extended(delta)
        ctx.store_index(key, index)
        if store is not None:
            if store.has(model.ref, base_fp):
                store.append_segment(model.ref, base_fp, fingerprint,
                                     delta)
            else:
                store.put(model.ref, fingerprint, index.raw)
        return index, "appended"

    vectors = llm_embedding(ctx, model_spec, texts)
    index = VectorIndex(vectors)
    ctx.store_index(key, index)
    if store is not None:
        store.put(model.ref, fingerprint, vectors)
    return index, "built"
