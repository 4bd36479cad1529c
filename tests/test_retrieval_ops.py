"""Retrieval-operator equivalence suite (ISSUE 5).

Pins the contracts of the first-class retrieval plan operators:

  * ``vector_topk`` / ``bm25_topk`` / ``hybrid_topk`` + ``llm_rerank``
    produce rows bit-identical to the imperative
    BM25Index/VectorIndex/fusion composition they replace;
  * the optimizer's corpus-filter pushdown (``prune_corpus``) embeds
    strictly fewer docs without changing a single output row, and
    query-side relational filters push below the LATERAL expansion;
  * ``IndexStore`` memoises built indexes across sessions (zero embed
    requests on reuse), recovers from a corrupt sidecar, prunes model
    re-versions, and stays bounded;
  * embed dispatches are batch-planned (no single mega-batch), feed the
    calibration sidecar, honour headroom, and co-pack deterministically
    under concurrent dispatch;
  * ``core.fusion`` edge cases: all-NaN columns, single retriever, rrf
    tie ranks, degenerate combmnz.
"""

import json
import threading

import numpy as np
import pytest

from repro.core import (MockProvider, PredictionCache, RequestScheduler,
                        SemanticContext, corpus_fingerprint, llm_embedding,
                        rrf, telemetry)
from repro.core.cache import IndexStore
from repro.core.fusion import (combanz, combmed, combmnz, combsum,
                               fusion)
from repro.core.resources import Catalog
from repro.engine import Pipeline, Table
from repro.retrieval import BM25Index, VectorIndex, active_mesh, \
    ensure_index

EMB = {"model": "e", "embedding_dim": 16, "context_window": 4096}
CHAT = {"model": "m", "context_window": 8192, "max_output_tokens": 16}


def make_corpus(n=48):
    topics = ("joins", "indexes", "vectors")
    return Table({
        "content": [f"doc {i} about {topics[i % 3]} with a body of "
                    f"searchable text" for i in range(n)],
        "year": [2000 + i % 6 for i in range(n)],
    })


def queries_table():
    return Table({"q": ["join algorithms", "vector search"],
                  "qid": [0, 1]})


# ---------------------------------------------------------------------------
# fusion hardening (satellite)
# ---------------------------------------------------------------------------
def test_fusion_all_nan_column_contributes_nothing():
    a = np.array([3.0, 1.0, 2.0])
    nan = np.full(3, np.nan)
    np.testing.assert_allclose(rrf(a, nan), rrf(a))
    np.testing.assert_allclose(combsum(a, nan), a)
    np.testing.assert_allclose(combanz(a, nan), a)
    for fn in (rrf, combsum, combmnz, combmed, combanz):
        out = fn(nan, nan)
        assert not np.isnan(out).any()
        np.testing.assert_allclose(out, 0.0)


def test_fusion_single_retriever_input():
    a = np.array([0.5, 2.0, 1.0])
    for m in ("rrf", "combsum", "combmnz", "combmed", "combanz"):
        out = fusion(m, a)
        assert out.shape == a.shape
        assert not np.isnan(out).any()
        # fusion of one retriever preserves its ranking
        assert list(np.argsort(-out, kind="stable")) == [1, 2, 0]


def test_rrf_tied_scores_share_rank():
    f = rrf(np.array([5.0, 5.0, 3.0, 3.0, 1.0]))
    assert f[0] == f[1]
    assert f[2] == f[3]
    assert f[0] > f[2] > f[4]
    # competition ranks: the group AFTER a tie keeps its absolute rank
    np.testing.assert_allclose(f, [1 / 61, 1 / 61, 1 / 63, 1 / 63,
                                   1 / 65])


def test_rrf_independent_of_tie_reporting_order():
    a = np.array([2.0, 2.0, 2.0, 1.0])
    b = a[[2, 0, 1, 3]]
    np.testing.assert_allclose(rrf(a)[3], rrf(b)[3])
    assert len({x for x in rrf(a)[:3]}) == 1


def test_combmnz_zero_non_nan_rows_are_exact_zero():
    m1 = np.array([np.nan, 1.0])
    m2 = np.array([np.nan, 2.0])
    out = combmnz(m1, m2)
    assert out[0] == 0.0
    assert out[1] == pytest.approx(6.0)        # (1+2) * 2 non-zero


def test_fusion_input_validation():
    with pytest.raises(ValueError):
        fusion("rrf")                          # no columns at all
    with pytest.raises(ValueError):
        combsum(np.ones(3), np.ones(4))        # ragged
    for m in ("rrf", "combsum", "combmnz", "combmed", "combanz"):
        assert fusion(m, np.array([])).shape == (0,)


# ---------------------------------------------------------------------------
# operator equivalence vs the imperative composition
# ---------------------------------------------------------------------------
def _imperative_hybrid(ctx, corpus, query, k, c, doc_col="content"):
    """The pre-PR idiom (examples/hybrid_search.py): separate retriever
    calls, full-length NaN-holed score arrays, fusion, final argsort."""
    texts = [str(x) for x in corpus.column(doc_col)]
    n = len(texts)
    vi = VectorIndex(llm_embedding(ctx, EMB, texts))
    qv = llm_embedding(ctx, EMB, [query])
    v_s, v_idx = vi.topk(qv, c)
    bm = BM25Index.build(texts)
    b_scores = bm.score(query)
    b_top = np.argsort(-b_scores, kind="stable")[:c]
    col_b = np.full(n, np.nan)
    col_b[b_top] = b_scores[b_top]
    col_v = np.full(n, np.nan)
    col_v[v_idx[0]] = v_s[0]
    fused = rrf(col_b, col_v)
    order = np.argsort(-fused, kind="stable")[:k]
    return [int(i) for i in order], [float(fused[i]) for i in order]


def test_vector_topk_matches_imperative():
    corpus = make_corpus()
    ctx = SemanticContext(provider=MockProvider())
    t = (Pipeline(ctx, queries_table(), "queries")
         .vector_topk("score", EMB, "q", corpus, k=5, doc_col="content")
         .collect())
    assert len(t) == 10
    ctx2 = SemanticContext(provider=MockProvider())
    texts = [str(x) for x in corpus.column("content")]
    vi = VectorIndex(llm_embedding(ctx2, EMB, texts))
    qv = llm_embedding(ctx2, EMB,
                       [str(q) for q in queries_table().column("q")])
    s, i = vi.topk(qv, 5)
    assert t.column("content") == [texts[j] for r in range(2)
                                   for j in i[r]]
    np.testing.assert_allclose(t.column("score"),
                               [float(x) for r in range(2) for x in s[r]])
    assert t.column("score_rank") == [1, 2, 3, 4, 5] * 2


def test_bm25_topk_matches_imperative():
    corpus = make_corpus()
    ctx = SemanticContext(provider=MockProvider())
    t = (Pipeline(ctx, queries_table(), "queries")
         .bm25_topk("bscore", "q", corpus, k=4, doc_col="content")
         .collect())
    assert ctx.provider.stats.calls == 0       # no LLM at all
    texts = [str(x) for x in corpus.column("content")]
    bm = BM25Index.build(texts)
    expected_docs, expected_scores = [], []
    for q in queries_table().column("q"):
        s = bm.score(str(q))
        order = np.argsort(-s, kind="stable")[:4]
        expected_docs += [texts[i] for i in order]
        expected_scores += [float(s[i]) for i in order]
    assert t.column("content") == expected_docs
    np.testing.assert_allclose(t.column("bscore"), expected_scores)


def test_hybrid_topk_plus_rerank_bit_identical_to_imperative():
    corpus = make_corpus()
    k, c = 6, 12
    ctx = SemanticContext(provider=MockProvider())
    pipe = (Pipeline(ctx, queries_table(), "queries")
            .hybrid_topk("score", EMB, "q", corpus, k=k,
                         doc_col="content", candidate_k=c)
            .llm_rerank(CHAT, {"prompt": "most relevant"},
                        ["content"], by="q"))
    t = pipe.collect()

    from repro.core import llm_rerank as llm_rerank_fn
    ctx2 = SemanticContext(provider=MockProvider())
    texts = [str(x) for x in corpus.column("content")]
    exp_content, exp_scores = [], []
    for q in queries_table().column("q"):
        ids, scores = _imperative_hybrid(ctx2, corpus, str(q), k, c)
        docs = [{"content": texts[i]} for i in ids]
        perm = llm_rerank_fn(ctx2, CHAT, {"prompt": "most relevant"},
                             docs)
        exp_content += [texts[ids[p]] for p in perm]
        exp_scores += [scores[p] for p in perm]
    assert t.column("content") == exp_content
    np.testing.assert_allclose(t.column("score"), exp_scores)
    # the plan embeds BOTH queries in one dispatch where the imperative
    # loop pays one per query: never more embed requests than imperative
    emb_reqs = sum(r.requests for r in ctx.reports
                   if r.function == "embedding")
    emb_reqs2 = sum(r.requests for r in ctx2.reports
                    if r.function == "embedding")
    assert 0 < emb_reqs <= emb_reqs2


def test_hybrid_fusion_methods_dispatch():
    corpus = make_corpus(24)
    for method in ("combsum", "combmnz"):
        ctx = SemanticContext(provider=MockProvider())
        t = (Pipeline(ctx, queries_table(), "queries")
             .hybrid_topk("score", EMB, "q", corpus, k=3,
                          doc_col="content", fusion=method,
                          candidate_k=8)
             .collect())
        assert len(t) == 6
        assert not np.isnan(t.column("score")).any()


def test_retrieval_empty_query_table_keeps_schema():
    corpus = make_corpus(8)
    ctx = SemanticContext(provider=MockProvider())
    t = (Pipeline(ctx, Table({"q": [], "qid": []}), "queries")
         .hybrid_topk("score", EMB, "q", corpus, k=3, doc_col="content")
         .collect())
    assert len(t) == 0
    assert set(t.column_names) >= {"q", "content", "score", "score_rank"}


def test_doc_column_collision_gets_suffix():
    corpus = Table({"content": ["a b", "b c"], "qid": [7, 8]})
    ctx = SemanticContext(provider=MockProvider())
    t = (Pipeline(ctx, queries_table(), "queries")
         .bm25_topk("s", "q", corpus, k=1, doc_col="content")
         .collect())
    assert "qid_doc" in t.column_names          # corpus qid renamed
    assert t.column("qid") == [0, 1]            # parent qid intact


# ---------------------------------------------------------------------------
# optimizer: corpus-filter pushdown, query-filter pushdown, k-pushdown
# ---------------------------------------------------------------------------
def _embedded_texts(ctx):
    return sum(r.n_tuples for r in ctx.reports
               if r.function == "embedding")


def test_corpus_filter_pushdown_preserves_results():
    corpus = make_corpus(60)
    flt = lambda r: r["year"] >= 2003

    def run(optimize):
        ctx = SemanticContext(provider=MockProvider())
        pipe = (Pipeline(ctx, queries_table(), "queries")
                .hybrid_topk("score", EMB, "q", corpus, k=5,
                             doc_col="content", candidate_k=10,
                             corpus_filter=flt,
                             corpus_filter_cols=["year"]))
        t = pipe.collect(optimize=optimize)
        return t.rows(), _embedded_texts(ctx), pipe

    rows_naive, embeds_naive, _ = run(False)
    rows_opt, embeds_opt, pipe = run(True)
    assert rows_opt == rows_naive
    assert embeds_opt < embeds_naive
    assert any(rw.startswith("prune_corpus")
               for rw in pipe._plan().rewrites)
    assert all(r["year"] >= 2003 for r in rows_opt)


def test_corpus_filter_pushdown_vector_topk_preserves_results():
    corpus = make_corpus(40)
    flt = lambda r: "joins" in r["content"]

    def run(optimize):
        ctx = SemanticContext(provider=MockProvider())
        return (Pipeline(ctx, queries_table(), "queries")
                .vector_topk("score", EMB, "q", corpus, k=4,
                             doc_col="content", corpus_filter=flt,
                             corpus_filter_cols=["content"])
                .collect(optimize=optimize)).rows()

    assert run(True) == run(False)


def test_query_side_filter_pushes_below_retrieval():
    corpus = make_corpus(30)

    def build(ctx):
        return (Pipeline(ctx, queries_table(), "queries")
                .hybrid_topk("score", EMB, "q", corpus, k=4,
                             doc_col="content", candidate_k=8)
                .filter(lambda r: r["qid"] == 0, cols=["qid"]))

    ctx = SemanticContext(provider=MockProvider())
    pipe = build(ctx)
    rows_opt = pipe.collect().rows()
    assert any("pushdown(filter before hybrid_topk)" in rw
               for rw in pipe._plan().rewrites)
    ctx2 = SemanticContext(provider=MockProvider())
    rows_naive = build(ctx2).collect(optimize=False).rows()
    assert rows_opt == rows_naive
    # pushed-down plan embeds only the surviving query
    assert _embedded_texts(ctx) < _embedded_texts(ctx2)


def test_filter_on_retrieval_outputs_stays_above():
    corpus = make_corpus(30)
    ctx = SemanticContext(provider=MockProvider())
    pipe = (Pipeline(ctx, queries_table(), "queries")
            .bm25_topk("score", "q", corpus, k=5, doc_col="content")
            .filter(lambda r: r["score_rank"] <= 2,
                    cols=["score_rank"]))
    plan = pipe._plan()
    assert not any("pushdown(filter before bm25_topk)" in rw
                   for rw in plan.rewrites)
    t = pipe.collect()
    assert len(t) == 4                          # 2 queries x top-2


def test_k_pushdown_sets_candidate_depth():
    corpus = make_corpus(300)
    ctx = SemanticContext(provider=MockProvider())
    pipe = (Pipeline(ctx, queries_table(), "queries")
            .hybrid_topk("score", EMB, "q", corpus, k=4,
                         doc_col="content"))
    plan = pipe._plan()
    assert any(rw.startswith("k_pushdown(hybrid_topk") for rw in
               plan.rewrites)
    node = [n for n in plan.nodes if n.op == "hybrid_topk"][0]
    assert node.info["candidate_k"] == 32       # max(32, 4*4)
    t = pipe.collect()
    assert len(t) == 8
    # the logical plan is untouched (candidate_k stays engine-chosen)
    assert pipe.nodes[1].info["candidate_k"] is None


def test_shared_corpus_embeds_once_and_is_noted():
    corpus = make_corpus(36)
    ctx = SemanticContext(provider=MockProvider(), enable_cache=False)
    pipe = (Pipeline(ctx, queries_table(), "queries")
            .vector_topk("s1", EMB, "q", corpus, k=3, doc_col="content")
            .vector_topk("s2", EMB, "q", corpus, k=3, doc_col="content"))
    plan = pipe._plan()
    assert any(rw.startswith("dedupe_corpus_embed")
               for rw in plan.rewrites)
    # cost model charges the corpus embed once: second node is cheaper
    reqs = [c["requests"] for c in plan.optimized_node_costs[1:3]]
    assert reqs[1] < reqs[0]
    t = pipe.collect()
    # runtime: the session index registry served the second node's
    # corpus (the prediction cache is off, so reuse is the registry's
    # doing) — embedded texts are the corpus ONCE, the 2 query rows of
    # node 1, and node 2's 6 expanded query rows (2 queries x 3 docs)
    assert _embedded_texts(ctx) == len(corpus) + 2 + 6
    assert len(t) == 18                         # 6 rows x 3 docs each


def test_explain_reports_retrieval_cost():
    corpus = make_corpus(50)
    with RequestScheduler(pack_linger_s=0.2) as sched:
        ctx = SemanticContext(provider=MockProvider(), scheduler=sched)
        pipe = (Pipeline(ctx, queries_table(), "queries")
                .hybrid_topk("score", EMB, "q", corpus, k=4,
                             doc_col="content", candidate_k=8)
                .llm_rerank(CHAT, {"prompt": "rank"}, ["content"],
                            by="q"))
        text = pipe.explain()
    assert "scan_flops=" in text                # index-scan cost
    assert "req=" in text                       # embed request estimate
    assert "hybrid_topk" in text


def test_explain_embed_estimate_drops_after_index_is_built():
    corpus = make_corpus(40)
    ctx = SemanticContext(provider=MockProvider())

    def build():
        return (Pipeline(ctx, queries_table(), "queries")
                .vector_topk("score", EMB, "q", corpus, k=3,
                             doc_col="content"))

    before = build()._plan().optimized_node_costs[1]["requests"]
    build().collect()
    after = build()._plan().optimized_node_costs[1]["requests"]
    assert after < before                       # corpus index memoised


# ---------------------------------------------------------------------------
# IndexStore sidecar
# ---------------------------------------------------------------------------
def test_index_store_reuse_across_sessions(tmp_path):
    corpus = make_corpus(20)
    texts = [str(x) for x in corpus.column("content")]
    cache_path = str(tmp_path / "cache.jsonl")

    ctx1 = SemanticContext(
        provider=MockProvider(),
        cache=PredictionCache(persist_path=cache_path))
    idx1, src1 = ensure_index(ctx1, EMB, texts)
    assert src1 == "built"
    calls1 = ctx1.provider.stats.calls
    assert calls1 > 0

    # fresh session, fresh provider, fresh prediction cache object: the
    # vectors come from the index sidecar, zero provider calls
    ctx2 = SemanticContext(
        provider=MockProvider(),
        cache=PredictionCache(persist_path=str(tmp_path / "other.jsonl")),
        index_path=str(cache_path) + ".index.json")
    idx2, src2 = ensure_index(ctx2, EMB, texts)
    assert src2 == "store"
    assert ctx2.provider.stats.calls == 0
    np.testing.assert_array_equal(idx1.vectors, idx2.vectors)

    # and the session registry serves the third lookup
    _, src3 = ensure_index(ctx2, EMB, texts)
    assert src3 == "session"


def test_index_store_corruption_recovery(tmp_path):
    path = tmp_path / "idx.json"
    path.write_text("{not json")
    store = IndexStore(str(path))
    assert store.keys() == []
    store.put("e@0", "fp", np.ones((2, 4), np.float32))
    assert store.get("e@0", "fp").shape == (2, 4)
    # a half-valid file keeps the valid entries only
    path.write_text(json.dumps({"indexes": {
        "ok|fp": {"vectors": [[1.0, 2.0]]},
        "bad|fp": {"vectors": [[1.0], [2.0, 3.0]]},      # ragged
        "worse|fp": {"vectors": "nope"},
    }}))
    store2 = IndexStore(str(path))
    assert store2.keys() == ["ok|fp"]


def test_index_store_prunes_reversioned_models(tmp_path):
    store = IndexStore(str(tmp_path / "idx.json"))
    store.put("m@1", "fp", np.ones((1, 2), np.float32))
    store.put("inline@0", "fp2", np.ones((1, 2), np.float32))
    cat = Catalog()
    cat.create_model("m", arch="mock")
    cat.update_model("m", context_window=999)    # now m@2
    store.prune(cat)
    assert store.get("m@1", "fp") is None
    assert store.get("inline@0", "fp2") is not None


def test_index_store_capacity_bound(tmp_path):
    store = IndexStore(str(tmp_path / "idx.json"), capacity=2)
    for i in range(4):
        store.put("e@0", f"fp{i}", np.ones((1, 2), np.float32))
    assert len(store.keys()) == 2
    assert store.get("e@0", "fp3") is not None
    assert store.get("e@0", "fp0") is None


def test_index_roundtrip_is_bit_exact(tmp_path):
    rng = np.random.default_rng(0)
    v = rng.standard_normal((6, 8)).astype(np.float32)
    store = IndexStore(str(tmp_path / "idx.json"))
    store.put("e@0", "fp", v)
    reloaded = IndexStore(str(tmp_path / "idx.json")).get("e@0", "fp")
    np.testing.assert_array_equal(v, reloaded)


# ---------------------------------------------------------------------------
# llm_embedding: planned batches, headroom, calibration (satellite)
# ---------------------------------------------------------------------------
def test_embedding_dispatch_is_batch_planned():
    ctx = SemanticContext(provider=MockProvider())
    texts = [f"passage number {i} with a reasonably long body of text"
             for i in range(40)]
    model = {"model": "e", "embedding_dim": 8, "context_window": 200}
    llm_embedding(ctx, model, texts)
    rep = ctx.reports[-1]
    assert rep.requests > 1                     # no single mega-batch
    assert sum(rep.batch_sizes) == len(texts)
    assert len(rep.latencies) == rep.requests
    # calibration learned the embedding batch sizes
    rec = ctx.calibration_stats["e@0"]
    assert rec["requests"] == rep.requests
    assert rec["tuples"] == len(texts)


def test_embedding_respects_headroom():
    texts = [f"passage number {i} with a reasonably long body of text"
             for i in range(30)]
    model = {"model": "e", "embedding_dim": 8, "context_window": 400}
    ctx = SemanticContext(provider=MockProvider())
    llm_embedding(ctx, model, texts)
    full = ctx.reports[-1].batch_sizes
    ctx2 = SemanticContext(provider=MockProvider())
    ctx2.record_calibration("e@0", requests=8, retries=8, tuples=64,
                            latencies=[])
    ctx2.refresh_headroom()
    assert ctx2.batch_headroom("e@0") == 0.5
    llm_embedding(ctx2, model, texts)
    half = ctx2.reports[-1].batch_sizes
    assert max(half) < max(full)


def test_embedding_scheduler_counts_match_serial_with_batches():
    texts = [f"passage {i} body" for i in range(24)]
    model = {"model": "e", "embedding_dim": 8, "context_window": 48}
    ctx_s = SemanticContext(provider=MockProvider())
    ref = llm_embedding(ctx_s, model, texts)
    with RequestScheduler() as sched:
        ctx_c = SemanticContext(provider=MockProvider(), scheduler=sched)
        out = llm_embedding(ctx_c, model, texts)
    assert (out == ref).all()
    assert ctx_c.provider.stats.calls == ctx_s.provider.stats.calls
    assert ctx_s.provider.stats.calls > 1


# ---------------------------------------------------------------------------
# embed co-packing determinism under concurrency
# ---------------------------------------------------------------------------
def test_embedding_nodes_copack_fewer_requests_same_rows():
    # 24 rows x ~18 tokens at a 400-token window: each node plans one
    # full batch plus a 2-row tail; the tails are light enough to merge
    # into ONE co-packed request
    table = Table({
        "a": [f"first text {i} with a body of text" for i in range(24)],
        "b": [f"second text {i} with a body of text" for i in range(24)],
    })
    model = {"model": "e", "embedding_dim": 8, "context_window": 400,
             "max_concurrency": 8}

    def build(ctx):
        return (Pipeline(ctx, table, "docs")
                .llm_embedding("ea", model, ["a"])
                .llm_embedding("eb", model, ["b"]))

    runs = {}
    for copack in (False, True):
        with RequestScheduler(pack_linger_s=0.3) as sched:
            ctx = SemanticContext(provider=MockProvider(),
                                  scheduler=sched, copack=copack,
                                  enable_cache=False)
            t = build(ctx).collect(optimize=False)
            runs[copack] = (np.asarray(t.column("ea")),
                            np.asarray(t.column("eb")),
                            ctx.provider.stats.calls,
                            sched.stats.packed_requests)
    ea_off, eb_off, calls_off, _ = runs[False]
    ea_on, eb_on, calls_on, packed = runs[True]
    np.testing.assert_array_equal(ea_on, ea_off)
    np.testing.assert_array_equal(eb_on, eb_off)
    assert calls_on < calls_off
    assert packed >= 1


def test_retrieval_corpus_query_copack_deterministic_stress():
    corpus = Table({"content": [
        f"doc {i} about joins with a padded body of text"
        for i in range(55)]})
    queries = Table({"q": ["join algorithms", "index structures"]})
    model = {"model": "e", "embedding_dim": 8, "context_window": 300,
             "max_concurrency": 8}

    ctx_ref = SemanticContext(provider=MockProvider(),
                              enable_cache=False)
    ref = (Pipeline(ctx_ref, queries, "queries")
           .vector_topk("score", model, "q", corpus, k=5,
                        doc_col="content")
           .collect(optimize=False)).rows()
    for trial in range(4):
        with RequestScheduler(pack_linger_s=0.3) as sched:
            ctx = SemanticContext(provider=MockProvider(),
                                  scheduler=sched, enable_cache=False)
            rows = (Pipeline(ctx, queries, "queries")
                    .vector_topk("score", model, "q", corpus, k=5,
                                 doc_col="content")
                    .collect(optimize=False)).rows()
        assert rows == ref, f"trial {trial} diverged"


# ---------------------------------------------------------------------------
# grouped rerank + mesh-aware index
# ---------------------------------------------------------------------------
def test_llm_rerank_by_group_matches_per_group_rerank():
    from repro.core import llm_rerank as llm_rerank_fn
    table = Table({"g": [0, 0, 0, 1, 1, 1],
                   "content": [f"doc {i}" for i in range(6)]})
    ctx = SemanticContext(provider=MockProvider())
    t = (Pipeline(ctx, table, "docs")
         .llm_rerank(CHAT, {"prompt": "rank"}, ["content"], by="g")
         .collect())
    ctx2 = SemanticContext(provider=MockProvider())
    expected = []
    for g in (0, 1):
        docs = [{"content": f"doc {i}"} for i in range(3 * g, 3 * g + 3)]
        perm = llm_rerank_fn(ctx2, CHAT, {"prompt": "rank"}, docs)
        expected += [docs[p]["content"] for p in perm]
    assert t.column("content") == expected
    assert t.column("g") == [0, 0, 0, 1, 1, 1]


def test_vector_index_sharded_path_matches_oracle():
    import jax
    from jax.sharding import Mesh
    rng = np.random.default_rng(0)
    vectors = rng.standard_normal((64, 16)).astype(np.float32)
    q = rng.standard_normal((3, 16)).astype(np.float32)
    plain = VectorIndex(vectors)
    s_ref, i_ref = plain.topk(q, 5)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1,), ("data",))
    sharded = VectorIndex(vectors, mesh=mesh)
    s, i = sharded.topk(q, 5)
    np.testing.assert_array_equal(i, i_ref)
    np.testing.assert_allclose(s, s_ref, rtol=1e-5, atol=1e-6)
    # auto-detection ignores single-device meshes (sharding over one
    # device only adds dispatch overhead)
    with jax.set_mesh(mesh):
        assert active_mesh() is None


def test_corpus_fingerprint_is_order_sensitive():
    assert corpus_fingerprint(["a", "b"]) != corpus_fingerprint(["b", "a"])
    assert corpus_fingerprint(["a", "b"]) == corpus_fingerprint(["a", "b"])


def test_corpus_fingerprint_is_unambiguous():
    # length framing: no text content can fake a document boundary, so
    # distinct corpora never alias one registry/IndexStore key
    assert corpus_fingerprint(["a\x1fb"]) != corpus_fingerprint(["a", "b"])
    assert corpus_fingerprint(["a\x1f", "b"]) != \
        corpus_fingerprint(["a", "\x1fb"])
    assert corpus_fingerprint(["12", "3"]) != corpus_fingerprint(["1",
                                                                  "23"])


def test_select_pushdown_keeps_grouped_rerank_key():
    corpus = make_corpus(20)

    def build(ctx, select_cols):
        return (Pipeline(ctx, queries_table(), "queries")
                .bm25_topk("score", "q", corpus, k=3, doc_col="content")
                .llm_rerank(CHAT, {"prompt": "rank"}, ["content"],
                            by="q")
                .select(*select_cols))

    # a select that drops the group key must NOT push below the rerank
    ctx = SemanticContext(provider=MockProvider())
    pipe = build(ctx, ("content", "score"))
    rows_opt = pipe.collect().rows()        # KeyError before the fix
    assert not any("pushdown(select before llm_rerank)" in rw
                   for rw in pipe._plan().rewrites)
    ctx2 = SemanticContext(provider=MockProvider())
    assert rows_opt == build(ctx2, ("content", "score")) \
        .collect(optimize=False).rows()
    # one that keeps the key still pushes
    ctx3 = SemanticContext(provider=MockProvider())
    pipe3 = build(ctx3, ("q", "content"))
    rows3 = pipe3.collect().rows()
    assert any("pushdown(select before llm_rerank)" in rw
               for rw in pipe3._plan().rewrites)
    ctx4 = SemanticContext(provider=MockProvider())
    assert rows3 == build(ctx4, ("q", "content")) \
        .collect(optimize=False).rows()


# ---------------------------------------------------------------------------
# corpus fingerprint memo: one computation per Table column
# ---------------------------------------------------------------------------
def _fp_counts(before, after):
    """(fingerprint spans, memo reuses) between two telemetry snapshots."""
    def n(name):
        return (after.get(name, {"n": 0})["n"]
                - before.get(name, {"n": 0})["n"])
    return n("retrieval.fingerprint"), n("retrieval.fingerprint_reuse")


def test_two_plans_over_one_table_fingerprint_once():
    corpus = make_corpus()
    ctx = SemanticContext(provider=MockProvider())
    before = telemetry.snapshot()
    for _ in range(2):
        (Pipeline(ctx, queries_table(), "queries")
         .vector_topk("score", EMB, "q", corpus, k=5, doc_col="content")
         .collect())
    # plan 1's build computes; its executor and plan 2's build and
    # executor read the memo
    assert _fp_counts(before, telemetry.snapshot()) == (1, 3)
    texts, fp = corpus.text_fingerprint("content")
    assert isinstance(texts, tuple)
    assert list(texts) == [str(x) for x in corpus.column("content")]
    assert fp == corpus_fingerprint(texts)


@pytest.mark.parametrize("change", ["reassign", "append"])
def test_reassigned_or_appended_column_is_fingerprinted_afresh(change):
    corpus = make_corpus(12)
    _, fp0 = corpus.text_fingerprint("content")
    if change == "reassign":
        corpus.columns["content"] = [f"other {i}" for i in range(12)]
    else:
        corpus.columns["content"].append("one more doc")
    before = telemetry.snapshot()
    texts, fp = corpus.text_fingerprint("content")
    assert _fp_counts(before, telemetry.snapshot()) == (1, 0)
    assert list(texts) == corpus.column("content")
    assert fp == corpus_fingerprint(corpus.column("content")) != fp0
    before = telemetry.snapshot()
    assert corpus.text_fingerprint("content") == (texts, fp)
    assert _fp_counts(before, telemetry.snapshot()) == (0, 1)


@pytest.mark.parametrize("derive", ["with_column_same", "with_column_new",
                                    "filter"])
def test_derived_table_is_fingerprinted_afresh(derive):
    corpus = make_corpus(12)
    _, fp0 = corpus.text_fingerprint("content")
    if derive == "with_column_same":
        derived = corpus.with_column("tag", ["t"] * 12)
    elif derive == "with_column_new":
        derived = corpus.with_column("content",
                                     [f"new {i}" for i in range(12)])
    else:
        derived = corpus.filter(lambda r: r["year"] >= 2003)
    before = telemetry.snapshot()
    _, fp = derived.text_fingerprint("content")
    assert _fp_counts(before, telemetry.snapshot()) == (1, 0)
    assert fp == corpus_fingerprint(derived.column("content"))
    assert (fp == fp0) == (derive == "with_column_same")


@pytest.mark.parametrize("op", ["vector_topk", "bm25_topk", "hybrid_topk"])
def test_memoised_plan_rows_match_fresh_table(op):
    def plan(ctx, corpus):
        pipe = Pipeline(ctx, queries_table(), "queries")
        if op == "bm25_topk":
            pipe = pipe.bm25_topk("score", "q", corpus, k=5,
                                  doc_col="content")
        else:
            pipe = getattr(pipe, op)("score", EMB, "q", corpus, k=5,
                                     doc_col="content")
        return pipe.collect().rows()

    corpus = make_corpus()
    ctx = SemanticContext(provider=MockProvider())
    plan(ctx, corpus)
    before = telemetry.snapshot()
    memoised = plan(ctx, corpus)
    assert _fp_counts(before, telemetry.snapshot())[0] == 0
    fresh = Table({c: list(v) for c, v in corpus.columns.items()})
    assert memoised == plan(SemanticContext(provider=MockProvider()), fresh)
