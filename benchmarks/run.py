"""Benchmark harness — one function per paper table/claim.

Prints ``name,us_per_call,derived`` CSV rows.  The headline paper claims
reproduced here:

  * §2.3 batching: "up to 7x speedup for chat-completion map functions"
    -> bench_batching_chat_api (simulated per-request API latency, the
       paper's setting) and bench_batching_chat_local (real JAX provider —
       the TPU-native setting; speedup from dispatch amortisation)
  * §2.3 batching: "48x for embedding functions"
    -> bench_batching_embedding
  * §2.3 caching / dedup -> bench_caching, bench_dedup
  * async provider scheduler -> bench_scheduler (wall-clock vs
    max_concurrency on a latency-simulating MockProvider; emits
    machine-readable BENCH_scheduler.json next to this file)
  * speculative filter-chain dispatch -> bench_speculative (3-filter
    chain: k serial round-trips collapse to ~1, wasted requests within
    the selectivity-predicted budget, calibrated explain() wall-clock
    estimate within tolerance of measured; emits BENCH_speculative.json)
  * cross-node batch co-packing -> bench_copack (two map nodes sharing
    a metaprompt prefix: part-filled tail batches merge, mean batch
    fill strictly higher / requests strictly lower, bit-identical rows,
    packed wall-clock <= unpacked (deadline-aware last-tail-out flush);
    plus the calibration-aware headroom loop: observed overflow retries
    shrink the next session's planned batches; emits BENCH_copack.json)
  * first-class retrieval operators -> bench_rag (two-query hybrid
    plan: fewer embed requests from co-packing + IndexStore reuse,
    rows bit-identical to the imperative composition, retrieval cost
    in explain(), packed session wall-clock <= isolated sessions;
    emits BENCH_rag.json)
  * million-document retrieval -> bench_ann (100k-doc synthetic corpus:
    exact jnp scan vs Pallas-routed block-max scan vs IVF-ANN wall-clock
    + measured recall@10; incremental append embeds ONLY the delta vs a
    from-scratch rebuild — request/tuple counts asserted; emits
    BENCH_ann.json, recall gated by BENCH_ANN_RECALL_MIN)
  * Query 3 hybrid search -> bench_hybrid_search
  * serving engine -> bench_continuous_batching
  * kernels -> bench_kernel_* (interpret-mode correctness-path timing; the
    real perf story is the dry-run roofline in EXPERIMENTS.md)
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

import numpy as np


def _row(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def _timeit(fn, n=3, warmup=1):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n


# ---------------------------------------------------------------------------
def bench_batching_chat_api():
    """Paper setting: each request pays API overhead; batching packs tuples."""
    from repro.core import MockProvider, SemanticContext, llm_complete
    rows = [{"review": f"review text number {i} with some body"}
            for i in range(200)]
    model = {"model": "gpt-4o-mini", "context_window": 8192,
             "max_output_tokens": 8}
    times = {}
    for on in (False, True):
        # latency constants calibrated to the paper's API regime (~30 ms
        # request overhead, ~200 us/token service time): per-tuple work
        # ~4.6 ms vs 30 ms overhead -> ~7x from batching, the paper's
        # headline number
        ctx = SemanticContext(
            provider=MockProvider(latency_per_call_s=0.030,
                                  latency_per_token_s=0.0002),
            enable_batching=on, enable_cache=False, enable_dedup=False)
        dt = _timeit(lambda c=ctx: llm_complete(c, model,
                                                {"prompt": "classify"},
                                                rows), n=1, warmup=0)
        times[on] = dt
    speedup = times[False] / times[True]
    _row("batching_chat_api_off", times[False] * 1e6 / len(rows),
         f"requests={200}")
    _row("batching_chat_api_on", times[True] * 1e6 / len(rows),
         f"speedup={speedup:.1f}x(paper:7x)")
    return speedup


def bench_batching_chat_local():
    """TPU-native setting: real JAX provider; batching amortises dispatch."""
    from repro.core import SemanticContext, llm_complete
    from repro.core.provider import LocalJaxProvider
    rows = [{"t": f"row {i}"} for i in range(24)]
    model = {"model": "local", "context_window": 4096,
             "max_output_tokens": 2}
    prov = LocalJaxProvider("olmo-1b")
    times = {}
    for on in (False, True):
        ctx = SemanticContext(provider=prov, enable_batching=on,
                              enable_cache=False, enable_dedup=False)
        dt = _timeit(lambda c=ctx: llm_complete(
            c, model, {"prompt": "classify"}, rows), n=1, warmup=1)
        times[on] = dt
    _row("batching_chat_local", times[True] * 1e6 / len(rows),
         f"speedup={times[False]/times[True]:.1f}x")
    return times[False] / times[True]


def bench_batching_embedding():
    """Paper: 48x for embedding functions.  Real JAX embed path."""
    from repro.core import SemanticContext, llm_embedding
    from repro.core.provider import LocalJaxProvider
    texts = [f"passage number {i} about joins" for i in range(64)]
    model = {"model": "local-embed", "context_window": 4096}
    prov = LocalJaxProvider("olmo-1b")
    times = {}
    for on in (False, True):
        ctx = SemanticContext(provider=prov, enable_batching=on,
                              enable_cache=False, enable_dedup=False)
        dt = _timeit(lambda c=ctx: llm_embedding(c, model, texts),
                     n=1, warmup=1)
        times[on] = dt
    _row("batching_embedding", times[True] * 1e6 / len(texts),
         f"speedup={times[False]/times[True]:.1f}x(paper:48x)")
    return times[False] / times[True]


def bench_optimizer():
    """Cost-based plan rewriting (pushdown + fusion): naive vs optimized
    request/token counts on a 1k-row filter+complete+limit workload."""
    from repro.core import MockProvider, SemanticContext
    from repro.engine import Pipeline, Table

    n = 1000
    table = Table({
        "id": list(range(n)),
        "text": [f"review {i} about {'joins' if i % 4 else 'indexes'} "
                 f"with a reasonably long body of text" for i in range(n)],
        "year": [2000 + i % 25 for i in range(n)],
    })
    model = {"model": "m", "context_window": 4096, "max_output_tokens": 8}

    def make(ctx):
        return (Pipeline(ctx, table, "reviews")
                .llm_filter(model, {"prompt": "is about joins"}, ["text"])
                .llm_complete("summary", model, {"prompt": "summarize"},
                              ["text"])
                .llm_complete_json("meta", model, {"prompt": "extract"},
                                   ["text"])
                .order_by("year", desc=True)
                .limit(10))

    stats = {}
    for optimize in (False, True):
        ctx = SemanticContext(provider=MockProvider(), enable_cache=False,
                              enable_dedup=False)
        pipe = make(ctx)
        t0 = time.perf_counter()
        pipe.collect(optimize=optimize)
        dt = time.perf_counter() - t0
        est = (pipe._plan().optimized_cost if optimize
               else pipe._plan().naive_cost)
        stats[optimize] = (ctx.provider.stats.calls,
                           ctx.provider.stats.prompt_tokens, est, dt)
    req_n, tok_n, est_n, dt_n = stats[False]
    req_o, tok_o, est_o, dt_o = stats[True]
    _row("optimizer_naive", dt_n * 1e6 / n,
         f"requests={req_n} prompt_tokens={tok_n} est[{est_n}]")
    _row("optimizer_optimized", dt_o * 1e6 / n,
         f"requests={req_o} prompt_tokens={tok_o} est[{est_o}]")
    assert req_o < req_n and tok_o < tok_n, \
        "optimized plan must issue strictly fewer requests and tokens"
    assert est_o.requests < est_n.requests
    assert est_o.tokens < est_n.tokens
    _row("optimizer_reduction", 0.0,
         f"requests={req_n/max(req_o,1):.1f}x tokens={tok_n/max(tok_o,1):.1f}x")
    return req_n / max(req_o, 1)


def bench_scheduler():
    """Async provider scheduler: wall-clock vs max_concurrency on a
    multi-node plan over a latency-simulating MockProvider.  Results,
    request counts and token counts must be identical to the serial
    path — only the wall-clock may change (near-linearly with the
    concurrency limit, until the batch count per node caps the overlap).
    """
    from repro.core import MockProvider, RequestScheduler, SemanticContext
    from repro.engine import Pipeline, Table

    # 50 ms per request keeps dispatch overhead a small fraction of the
    # measured time, so the >=3x gate at concurrency 4 has real headroom
    # (ideal is 4x; thread wakeup costs eat ~1-3 ms per request)
    latency = 0.05
    n = 72
    table = Table({
        "text": [f"review number {i} with a moderately sized body of "
                 f"text to fill the context window" for i in range(n)],
    })
    # small window -> ~8 batches per node; 3 independent map nodes
    base = {"model": "m", "context_window": 700, "max_output_tokens": 8}

    def run(concurrency):
        sched = (RequestScheduler() if concurrency else None)
        model = dict(base, max_concurrency=concurrency or 1)
        ctx = SemanticContext(provider=MockProvider(
            latency_per_call_s=latency), scheduler=sched,
            enable_cache=False, enable_dedup=False)
        pipe = (Pipeline(ctx, table, "reviews")
                .llm_complete("summary", model, {"prompt": "summarize"},
                              ["text"])
                .llm_complete("topic", model, {"prompt": "name the topic"},
                              ["text"])
                .llm_complete_json("meta", model, {"prompt": "extract"},
                                   ["text"]))
        t0 = time.perf_counter()
        out = pipe.collect(optimize=False)
        dt = time.perf_counter() - t0
        if sched is not None:
            sched.shutdown()
        return (dt, out.rows(), ctx.provider.stats.calls,
                ctx.provider.stats.prompt_tokens)

    t_sync, rows_sync, req_sync, tok_sync = run(None)
    results = {"latency_per_call_s": latency, "rows": n, "nodes": 3,
               "sync": {"wall_s": round(t_sync, 4), "requests": req_sync,
                        "prompt_tokens": tok_sync}}
    for c in (1, 4, 16):
        dt, rows, req, tok = run(c)
        assert rows == rows_sync, "scheduled results differ from serial"
        assert (req, tok) == (req_sync, tok_sync), \
            f"request/token counts changed at concurrency {c}: " \
            f"{(req, tok)} != {(req_sync, tok_sync)}"
        results[f"concurrency_{c}"] = {
            "wall_s": round(dt, 4), "requests": req,
            "prompt_tokens": tok, "speedup": round(t_sync / dt, 2)}
        _row(f"scheduler_c{c}", dt * 1e6 / n,
             f"speedup={t_sync/dt:.1f}x requests={req}")
    speedup4 = results["concurrency_4"]["speedup"]
    out_path = Path(__file__).resolve().parent / "BENCH_scheduler.json"
    out_path.write_text(json.dumps(results, indent=1))
    # BENCH_SCHEDULER_MIN_SPEEDUP relaxes the gate on oversubscribed CI
    # runners where thread wakeups stretch past the simulated latency
    floor = float(os.environ.get("BENCH_SCHEDULER_MIN_SPEEDUP", "3.0"))
    assert speedup4 >= floor, \
        f"expected >={floor}x wall-clock reduction at max_concurrency=4, " \
        f"got {speedup4:.1f}x"
    _row("scheduler_sync", t_sync * 1e6 / n,
         f"requests={req_sync} json={out_path.name}")
    return speedup4


def bench_speculative():
    """Speculative filter-chain dispatch: a 3-filter llm_filter chain
    over a latency-simulating MockProvider, serial vs speculative.

    Serial chain execution pays one provider round-trip per member
    (each filter waits for its predecessor's survivors); speculation
    fans all members out over the chain input concurrently and ANDs
    the masks, collapsing the chain's critical path to ~1 round-trip.
    Asserts:

      * surviving rows are identical serial vs speculative,
      * the planner CHOOSES speculation from the calibrated cost model
        (a warmup run records selectivity + latency statistics),
      * measured wasted requests stay within the selectivity-predicted
        budget reported by explain(),
      * explain()'s calibrated wall-clock estimate for the speculative
        plan is within tolerance of the measured wall-clock,
      * speculative wall-clock beats serial by the configured floor.

    Two further scenarios exercise the cross-operator speculation
    shapes under ``speculate="auto"``:

      * **filter->map**: an ``llm_complete`` downstream of a 0.5
        selectivity ``llm_filter`` dispatches over the filter's full
        input concurrently with the mask; gated on
        ``wall_spec <= BENCH_SPEC_WALL_TOL x wall_serial``
        (default 0.6),
      * **retrieval->rerank**: an ``llm_rerank`` downstream of
        ``hybrid_topk`` warms its window cache over the BM25-predicted
        candidates while the dense embeds run; the corpus is crafted so
        the BM25 and fused orders agree (asserted as a precondition),
        gated on ``wall_spec <= BENCH_SPEC_RERANK_WALL_TOL x
        wall_serial`` (default 0.9).
    """
    import re as _re

    from repro.core import MockProvider, RequestScheduler, SemanticContext
    from repro.engine import Pipeline, Table

    # big enough that dispatch/GIL overhead (tens of ms across the
    # 12-request fan-out) stays a small fraction of each round-trip
    latency = 0.25
    n = 96

    def behaviour(kind, prefix, rows):
        # deterministic, content-based verdicts with known selectivity:
        # a filter prompt "contains <marker>" passes rows whose text
        # carries the marker
        if kind != "filter":
            return None
        marker = _re.search(r"contains (\w+)", prefix).group(1)
        return [f"{i}: {'true' if marker in r else 'false'}"
                for i, r in enumerate(rows)]

    table = Table({"text": [
        f"doc {i} {'alpha' if i % 3 else 'x'} "
        f"{'beta' if i % 2 == 0 else 'y'} "
        f"{'gamma' if i % 4 < 2 else 'z'} with a body of text"
        for i in range(n)]})

    # three DISTINCT models: semantic fusion would otherwise merge the
    # chain into one multi-task pass (same model + cols), and distinct
    # models fan out on independent concurrency gates
    def model(k):
        return {"model": f"spec-m{k}", "context_window": 100_000,
                "max_output_tokens": 8, "max_concurrency": 16}

    def build(ctx):
        return (Pipeline(ctx, table, "docs")
                .llm_filter(model(1), {"prompt": "contains alpha"},
                            ["text"])
                .llm_filter(model(2), {"prompt": "contains beta"},
                            ["text"])
                .llm_filter(model(3), {"prompt": "contains gamma"},
                            ["text"]))

    with RequestScheduler() as sched:
        ctx = SemanticContext(
            provider=MockProvider(behaviour, latency_per_call_s=latency),
            scheduler=sched, enable_cache=False, enable_dedup=False,
            max_batch=24)
        # warmup: records per-prompt selectivity and per-model latency
        # calibration — the statistics the speculation decision needs
        build(ctx).collect(speculate=False)

        c0 = ctx.provider.stats.calls
        t0 = time.perf_counter()
        rows_serial = build(ctx).collect(speculate=False).rows()
        dt_serial = time.perf_counter() - t0
        req_serial = ctx.provider.stats.calls - c0

        pipe = build(ctx)
        t0 = time.perf_counter()
        rows_spec = pipe.collect(speculate=True).rows()
        dt_spec = time.perf_counter() - t0
        req_spec = ctx.provider.stats.calls - c0 - req_serial

    assert rows_spec == rows_serial, \
        "speculative chain changed the surviving tuple stream"
    plan = pipe._plan(True)
    decisions = [d for d in plan.spec_decisions if d.chosen]
    assert decisions, "planner did not choose speculation: " + "; ".join(
        str(d) for d in plan.spec_decisions)
    d = decisions[0]
    wasted = req_spec - req_serial
    assert wasted <= d.wasted_requests, \
        f"measured waste {wasted} exceeds the selectivity-predicted " \
        f"budget {d.wasted_requests}"

    est_wall = plan.optimized_cost.wall_s
    assert est_wall > 0, "cost model stayed uncalibrated after warmup"
    est_err = abs(est_wall - dt_spec) / dt_spec
    # gates relaxable on oversubscribed CI runners (thread wakeups
    # stretch past the simulated provider latency)
    tol = float(os.environ.get("BENCH_SPECULATIVE_EST_TOL", "0.25"))
    floor = float(os.environ.get("BENCH_SPECULATIVE_MIN_SPEEDUP", "1.8"))
    speedup = dt_serial / dt_spec

    # -- scenario 2: map past filter at selectivity 0.5 -----------------
    table2 = Table({"text": [
        f"doc {i} {'alpha' if i % 2 == 0 else 'omega'} "
        f"with a body of text" for i in range(n)]})
    map_model = {"model": "spec-map", "context_window": 100_000,
                 "max_output_tokens": 16, "max_concurrency": 16}

    def build_map(ctx):
        return (Pipeline(ctx, table2, "docs")
                .llm_filter(model(1), {"prompt": "contains alpha"},
                            ["text"])
                .llm_complete("summary", map_model,
                              {"prompt": "summarize"}, ["text"]))

    with RequestScheduler() as sched:
        ctx = SemanticContext(
            provider=MockProvider(behaviour, latency_per_call_s=latency),
            scheduler=sched, enable_cache=False, enable_dedup=False,
            max_batch=24)
        # warmup: records the 0.5 mask density and per-model latency
        build_map(ctx).collect(speculate=False)

        c0 = ctx.provider.stats.calls
        t0 = time.perf_counter()
        rows_m_serial = build_map(ctx).collect(speculate=False).rows()
        dt_m_serial = time.perf_counter() - t0
        req_m_serial = ctx.provider.stats.calls - c0

        pipe_m = build_map(ctx)
        t0 = time.perf_counter()
        rows_m_spec = pipe_m.collect(speculate="auto").rows()
        dt_m_spec = time.perf_counter() - t0
        req_m_spec = ctx.provider.stats.calls - c0 - req_m_serial
        cancelled = sched.stats.spec_cancelled

    assert rows_m_spec == rows_m_serial, \
        "speculative map changed the output tuple stream"
    plan_m = pipe_m._plan("auto")
    dm = [x for x in plan_m.spec_decisions
          if x.kind == "map" and x.chosen]
    assert dm, "planner did not choose map speculation: " + "; ".join(
        str(x) for x in plan_m.spec_decisions)
    wasted_m = req_m_spec - req_m_serial
    assert wasted_m <= dm[0].wasted_requests, \
        f"measured map waste {wasted_m} exceeds the predicted budget " \
        f"{dm[0].wasted_requests}"
    wall_tol = float(os.environ.get("BENCH_SPEC_WALL_TOL", "0.6"))
    _row("speculative_map_serial", dt_m_serial * 1e6 / n,
         f"requests={req_m_serial}")
    _row("speculative_map_spec", dt_m_spec * 1e6 / n,
         f"requests={req_m_spec} wasted={wasted_m} "
         f"cancelled={cancelled} "
         f"speedup={dt_m_serial / dt_m_spec:.1f}x")

    # -- scenario 3: retrieval-aware rerank -----------------------------
    # the corpus is crafted (per-doc salts searched offline) so the
    # mock embedding similarities RANK the matching docs in the same
    # order as their BM25 term-frequency scores: the fused top-k then
    # equals the BM25-predicted top-k and warmup window-cache entries
    # byte-match the authoritative rerank's windows
    k_rr, cand_rr = 6, 12
    docs_rr = [
        "join algorithms " * (k_rr - i) + f"candidate document {i} s{s}"
        for i, s in enumerate((0, 91, 9, 41, 51, 1))
    ] + [
        f"unrelated storage passage number {i} s{s}"
        for i, s in zip(range(6, 24),
                        (1, 3, 0, 0, 0, 0, 0, 0, 0, 1, 1, 0,
                         0, 0, 0, 0, 2, 1))
    ]
    docs_rr = [t.strip() for t in docs_rr]
    corpus_rr = Table({"content": docs_rr})
    queries_rr = Table({"q": ["join algorithms"], "qid": [0]})
    emb_model = {"model": "spec-emb", "embedding_dim": 16,
                 "context_window": 4096}
    rr_model = {"model": "spec-rr", "context_window": 100_000,
                "max_output_tokens": 16, "max_concurrency": 8}

    def build_rr(ctx):
        return (Pipeline(ctx, queries_rr, "queries")
                .hybrid_topk("score", emb_model, "q", corpus_rr,
                             k=k_rr, doc_col="content",
                             candidate_k=cand_rr)
                .llm_rerank(rr_model, {"prompt": "most relevant"},
                            ["content"], by="q"))

    # precondition: BM25 order must match the fused order, else the
    # warmup cannot hit and the scenario silently degrades to serial
    pre = (Pipeline(SemanticContext(provider=MockProvider()),
                    queries_rr, "queries")
           .hybrid_topk("score", emb_model, "q", corpus_rr, k=k_rr,
                        doc_col="content", candidate_k=cand_rr)
           .collect(speculate=False))
    assert [r["content"] for r in pre.rows()] == docs_rr[:k_rr], \
        "crafted corpus drifted: fused top-k no longer equals the " \
        "BM25 prediction (re-search the per-doc salts)"

    def run_rr(speculate):
        with RequestScheduler() as sched:
            ctx = SemanticContext(
                provider=MockProvider(latency_per_call_s=latency),
                scheduler=sched, speculate=speculate)
            pipe = build_rr(ctx)
            t0 = time.perf_counter()
            out = pipe.collect()
            dt = time.perf_counter() - t0
            return out.rows(), dt, pipe

    rows_r_serial, dt_r_serial, _ = run_rr(False)
    rows_r_spec, dt_r_spec, pipe_r = run_rr("auto")
    assert rows_r_spec == rows_r_serial, \
        "speculative rerank changed the reranked tuple stream"
    assert any(nd.op == "spec_rerank"
               for nd in pipe_r._executed_nodes), \
        "planner did not choose rerank speculation"
    rr_tol = float(os.environ.get("BENCH_SPEC_RERANK_WALL_TOL", "0.9"))
    _row("speculative_rerank_serial", dt_r_serial * 1e6,
         f"k={k_rr} candidate_k={cand_rr}")
    _row("speculative_rerank_spec", dt_r_spec * 1e6,
         f"overlap={1 - dt_r_spec / dt_r_serial:.0%}")

    results = {
        "latency_per_call_s": latency, "rows": n, "chain": 3,
        "serial": {"wall_s": round(dt_serial, 4), "requests": req_serial,
                   "waves_est": d.serial_waves,
                   "wall_est_s": round(d.serial_wall_s, 4)},
        "speculative": {"wall_s": round(dt_spec, 4),
                        "requests": req_spec,
                        "waves_est": d.spec_waves,
                        "wall_est_s": round(est_wall, 4)},
        "wasted_requests": wasted,
        "wasted_budget": d.wasted_requests,
        "speedup": round(speedup, 2),
        "est_wall_error": round(est_err, 3),
        # cross-operator scenarios (picked up by TRAJECTORY.json)
        "wall_serial_s": round(dt_m_serial, 4),
        "wall_spec_s": round(dt_m_spec, 4),
        "spec_cancelled": cancelled,
        "filter_map": {
            "selectivity": 0.5,
            "wall_serial_s": round(dt_m_serial, 4),
            "wall_spec_s": round(dt_m_spec, 4),
            "requests_serial": req_m_serial,
            "requests_spec": req_m_spec,
            "wasted_requests": wasted_m,
            "wasted_budget": dm[0].wasted_requests,
            "spec_cancelled": cancelled,
            "wall_ratio": round(dt_m_spec / dt_m_serial, 3),
        },
        "rerank": {
            "wall_serial_s": round(dt_r_serial, 4),
            "wall_spec_s": round(dt_r_spec, 4),
            "wall_ratio": round(dt_r_spec / dt_r_serial, 3),
            "overlap": round(1 - dt_r_spec / dt_r_serial, 3),
        },
    }
    out_path = Path(__file__).resolve().parent / "BENCH_speculative.json"
    out_path.write_text(json.dumps(results, indent=1))

    _row("speculative_serial", dt_serial * 1e6 / n,
         f"requests={req_serial} waves={d.serial_waves}")
    _row("speculative_spec", dt_spec * 1e6 / n,
         f"requests={req_spec} waves={d.spec_waves} "
         f"speedup={speedup:.1f}x wasted={wasted}/{d.wasted_requests} "
         f"json={out_path.name}")
    _row("speculative_estimate", est_wall * 1e6,
         f"est_wall_error={est_err:.1%}")
    assert est_err <= tol, \
        f"calibrated wall estimate {est_wall:.3f}s is {est_err:.0%} " \
        f"off measured {dt_spec:.3f}s (tolerance {tol:.0%})"
    assert speedup >= floor, \
        f"expected >={floor}x wall-clock reduction from speculation, " \
        f"got {speedup:.1f}x"
    assert dt_m_spec <= wall_tol * dt_m_serial, \
        f"filter->map speculative wall {dt_m_spec:.3f}s exceeds " \
        f"{wall_tol:.2f}x serial wall {dt_m_serial:.3f}s"
    assert dt_r_spec <= rr_tol * dt_r_serial, \
        f"retrieval->rerank speculative wall {dt_r_spec:.3f}s shows " \
        f"no overlap vs serial {dt_r_serial:.3f}s " \
        f"(tolerance {rr_tol:.2f}x)"
    return speedup


def bench_copack():
    """Cross-node batch co-packing: two map nodes sharing one metaprompt
    prefix (same model + prompt + kind over different columns) dispatch
    concurrently; with co-packing their part-filled tail batches merge
    into one provider request.  Asserts:

      * collected rows are bit-identical with co-packing on vs off,
      * total provider requests are strictly LOWER with co-packing on,
      * mean dispatched batch fill (tuples per request) is strictly
        HIGHER with co-packing on,
      * explain() reports the packed request estimate (packed_req <
        requests).

    Also measures the calibration-aware headroom loop on a tight-window
    workload: session 1 overflows (token estimates undercount the
    serialization framing) and records retries; session 2 loads the
    calibration sidecar, plans with headroom, and pays fewer
    split-and-requeue retries.
    """
    import tempfile

    from repro.core import (MockProvider, PredictionCache,
                            RequestScheduler, SemanticContext,
                            llm_complete)
    from repro.engine import Pipeline, Table

    n = 60
    max_batch = 24          # 60 rows -> [24, 24, 12]: part-filled tail
    table = Table({
        "a": [f"first column text number {i} with a body of text"
              for i in range(n)],
        "b": [f"second column text number {i} with a body of text"
              for i in range(n)],
    })
    model = {"model": "cp", "context_window": 100_000,
             "max_output_tokens": 8, "max_concurrency": 8}

    def build(ctx):
        return (Pipeline(ctx, table, "docs")
                .llm_complete("s1", model, {"prompt": "summarize"}, ["a"])
                .llm_complete("s2", model, {"prompt": "summarize"},
                              ["b"]))

    runs = {}
    explain_text = None
    packed_est = None
    for copack in (False, True):
        with RequestScheduler(pack_linger_s=0.5) as sched:
            ctx = SemanticContext(
                provider=MockProvider(latency_per_call_s=0.01),
                scheduler=sched, max_batch=max_batch, copack=copack)
            pipe = build(ctx)
            t0 = time.perf_counter()
            rows = pipe.collect(optimize=False).rows()
            dt = time.perf_counter() - t0
            tuples = sum(sum(r.batch_sizes) for r in ctx.reports)
            runs[copack] = {
                "rows": rows, "wall_s": dt,
                "requests": ctx.provider.stats.calls,
                "tuples_dispatched": tuples,
                "mean_fill": tuples / max(ctx.provider.stats.calls, 1),
                "packed_requests": sched.stats.packed_requests,
                "packed_batches": sched.stats.packed_batches,
            }
            if copack:
                explain_text = pipe.explain()
                plan = pipe._plan()
                packed_est = plan.optimized_cost.packed_requests
                est_requests = plan.optimized_cost.requests

    off, on = runs[False], runs[True]
    assert on["rows"] == off["rows"], \
        "co-packing changed the collected rows"
    assert on["requests"] < off["requests"], \
        f"expected strictly fewer requests with co-packing, got " \
        f"{on['requests']} vs {off['requests']}"
    assert on["mean_fill"] > off["mean_fill"], \
        f"expected strictly denser batches with co-packing, got " \
        f"{on['mean_fill']:.2f} vs {off['mean_fill']:.2f}"
    assert packed_est and packed_est < est_requests, \
        "explain() must report a packed request estimate below the " \
        "unpacked one"
    assert "packed_req=" in explain_text
    assert "Objectives:" in explain_text and "latency:" in explain_text \
        and "cost:" in explain_text, \
        "explain() must report both objective frontiers"

    # the packed path must also be the fast path: last-tail-out flushes
    # make co-packing free on wall-clock (tolerance for runner noise)
    wall_tol = float(os.environ.get("BENCH_COPACK_WALL_TOL", "1.10"))
    assert on["wall_s"] <= off["wall_s"] * wall_tol, \
        f"co-packing regressed wall-clock: {on['wall_s']:.3f}s packed " \
        f"vs {off['wall_s']:.3f}s unpacked (tolerance {wall_tol}x)"

    # calibration-aware headroom: overflow retries feed back into the
    # planner as a smaller budget the NEXT session
    with tempfile.TemporaryDirectory() as td:
        cache_path = f"{td}/cache.jsonl"
        tight = {"model": "tight", "context_window": 260,
                 "max_output_tokens": 2}
        retries = []
        for tag in ("alpha", "beta"):
            ctx = SemanticContext(
                cache=PredictionCache(persist_path=cache_path),
                provider=MockProvider(), enable_dedup=False)
            with ctx:
                llm_complete(ctx, tight, {"prompt": "p"},
                             [{"t": f"{tag} row {i} and padding {i}"}
                              for i in range(48)])
            retries.append(ctx.last_report().retries)
    assert retries[0] > 0 and retries[1] < retries[0], \
        f"headroom did not reduce overflow retries: {retries}"

    results = {
        "rows": n, "nodes": 2, "max_batch": max_batch,
        "copack_off": {k: v for k, v in off.items() if k != "rows"},
        "copack_on": {k: v for k, v in on.items() if k != "rows"},
        "packed_request_estimate": packed_est,
        "wall_packed_s": round(on["wall_s"], 4),
        "wall_unpacked_s": round(off["wall_s"], 4),
        "headroom": {"session1_retries": retries[0],
                     "session2_retries": retries[1]},
    }
    for r in (results["copack_off"], results["copack_on"]):
        r["wall_s"] = round(r["wall_s"], 4)
        r["mean_fill"] = round(r["mean_fill"], 2)
    out_path = Path(__file__).resolve().parent / "BENCH_copack.json"
    out_path.write_text(json.dumps(results, indent=1))

    _row("copack_off", off["wall_s"] * 1e6 / n,
         f"requests={off['requests']} fill={off['mean_fill']:.1f}")
    _row("copack_on", on["wall_s"] * 1e6 / n,
         f"requests={on['requests']} fill={on['mean_fill']:.1f} "
         f"packed_req_est={packed_est} json={out_path.name}")
    _row("copack_headroom", 0.0,
         f"retries_session1={retries[0]} retries_session2={retries[1]}")
    return off["requests"] / on["requests"]


def bench_rag():
    """First-class retrieval operators (paper Query 3 as a PLAN): a
    two-query hybrid workload — ``hybrid_topk`` -> ``llm_rerank`` per
    query over one corpus — run two ways:

      * OFF: per-query isolated session, no co-packing, no index store
        (the imperative pre-PR posture: every query re-embeds the
        corpus, corpus and query embeds ship separately);
      * ON: one session with the concurrent scheduler, embed co-packing
        and the ``IndexStore`` sidecar (query 1 builds the index and
        merges its corpus tail batch with the query embed; query 2
        reuses the index and embeds only the query).

    Asserts:

      * retrieved+reranked rows are bit-identical ON vs OFF and vs the
        imperative BM25Index/VectorIndex/fusion/llm_rerank composition,
      * provider embed requests are strictly FEWER with co-packing +
        index reuse ON,
      * ``explain()`` reports the retrieval cost: per-node embed request
        estimate (``req=``), the co-packed estimate (``packed_req=``)
        and the index-scan cost (``scan_flops=``).
    """
    import tempfile

    from repro.core import (MockProvider, RequestScheduler,
                            SemanticContext, llm_embedding, llm_rerank,
                            rrf)
    from repro.engine import Pipeline, Table
    from repro.retrieval import BM25Index, VectorIndex

    n_docs = 80
    topics = ("joins", "indexes", "vectors", "storage")
    corpus = Table({
        "content": [f"passage {i} about {topics[i % 4]} with a body of "
                    f"searchable text" for i in range(n_docs)],
        "kind": [topics[i % 4] for i in range(n_docs)],
    })
    queries = ["cyclic join algorithms", "vector index scans"]
    k, c = 5, 12
    # ~16-token docs at a 600-token window: the corpus plans two full
    # embed batches plus a part-filled tail that can merge with the
    # (tiny) query embed batch
    emb = {"model": "emb", "embedding_dim": 32, "context_window": 600,
           "max_concurrency": 8}
    chat = {"model": "chat", "context_window": 8192,
            "max_output_tokens": 16}

    def build(ctx, query):
        return (Pipeline(ctx, Table({"q": [query]}), "question")
                .hybrid_topk("score", emb, "q", corpus, k=k,
                             doc_col="content", candidate_k=c)
                .llm_rerank(chat, {"prompt": "most relevant to the "
                                             "question"},
                            ["content"], by="q"))

    def embed_requests(ctx):
        return sum(r.requests for r in ctx.reports
                   if r.function == "embedding")

    # OFF: isolated per-query sessions, serial, no index store
    rows_off, req_off = [], 0
    t0 = time.perf_counter()
    for q in queries:
        ctx = SemanticContext(provider=MockProvider(),
                              enable_cache=False, copack=False)
        rows_off.append(build(ctx, q).collect().rows())
        req_off += embed_requests(ctx)
    dt_off = time.perf_counter() - t0

    # ON: one session — scheduler + co-packing + IndexStore sidecar
    rows_on, per_query_req = [], []
    explain_text = None
    packed_est = est_requests = None
    with tempfile.TemporaryDirectory() as td:
        with RequestScheduler(pack_linger_s=0.5) as sched:
            ctx = SemanticContext(provider=MockProvider(),
                                  scheduler=sched, enable_cache=False,
                                  index_path=f"{td}/index.json")
            t0 = time.perf_counter()
            for qi, q in enumerate(queries):
                before = embed_requests(ctx)
                pipe = build(ctx, q)
                rows_on.append(pipe.collect().rows())
                per_query_req.append(embed_requests(ctx) - before)
                if qi == 0:
                    explain_text = pipe.explain()
                    plan = pipe._plan()
                    packed_est = plan.optimized_cost.packed_requests
                    est_requests = plan.optimized_cost.requests
                    scan_est = plan.optimized_cost.scan_flops
            dt_on = time.perf_counter() - t0
            req_on = sum(per_query_req)
            packed_batches = sched.stats.packed_batches

    assert rows_on == rows_off, \
        "co-packing + index reuse changed the retrieved rows"
    assert req_on < req_off, \
        f"expected strictly fewer embed requests, got {req_on} vs " \
        f"{req_off}"
    assert per_query_req[1] < per_query_req[0], \
        "index reuse did not reduce the second query's embed requests"
    assert packed_est and packed_est < est_requests, \
        "explain() must report a packed embed-request estimate below " \
        "the unpacked one"
    assert "packed_req=" in explain_text
    assert "scan_flops=" in explain_text
    assert scan_est > 0
    assert "Objectives:" in explain_text, \
        "explain() must report both objective frontiers"

    # latency contract: the packed session (co-packing + index reuse)
    # must not be slower than the isolated per-query sessions
    wall_tol = float(os.environ.get("BENCH_RAG_WALL_TOL", "1.10"))
    assert dt_on <= dt_off * wall_tol, \
        f"packed RAG session regressed wall-clock: {dt_on:.3f}s packed " \
        f"vs {dt_off:.3f}s unpacked (tolerance {wall_tol}x)"

    # imperative composition (the pre-PR idiom): same rows, bit for bit
    ictx = SemanticContext(provider=MockProvider(), enable_cache=False)
    texts = [str(x) for x in corpus.column("content")]
    for q, plan_rows in zip(queries, rows_on):
        vi = VectorIndex(llm_embedding(ictx, emb, texts))
        qv = llm_embedding(ictx, emb, [q])
        v_s, v_idx = vi.topk(qv, c)
        bm = BM25Index.build(texts)
        b_scores = bm.score(q)
        b_top = np.argsort(-b_scores, kind="stable")[:c]
        col_b = np.full(n_docs, np.nan)
        col_b[b_top] = b_scores[b_top]
        col_v = np.full(n_docs, np.nan)
        col_v[v_idx[0]] = v_s[0]
        fused = rrf(col_b, col_v)
        top = np.argsort(-fused, kind="stable")[:k]
        perm = llm_rerank(ictx, chat,
                          {"prompt": "most relevant to the question"},
                          [{"content": texts[i]} for i in top])
        imp = [(texts[top[p]], float(fused[top[p]])) for p in perm]
        got = [(r["content"], r["score"]) for r in plan_rows]
        assert got == imp, "plan rows diverge from the imperative " \
                           "composition"

    results = {
        "docs": n_docs, "queries": len(queries), "k": k,
        "candidate_k": c,
        "embed_requests_off": req_off,
        "embed_requests_on": req_on,
        "per_query_embed_requests_on": per_query_req,
        "packed_tail_batches": packed_batches,
        "packed_request_estimate": packed_est,
        "unpacked_request_estimate": est_requests,
        "scan_flops_estimate": scan_est,
        "wall_s_off": round(dt_off, 4), "wall_s_on": round(dt_on, 4),
        "wall_packed_s": round(dt_on, 4),
        "wall_unpacked_s": round(dt_off, 4),
    }
    out_path = Path(__file__).resolve().parent / "BENCH_rag.json"
    out_path.write_text(json.dumps(results, indent=1))

    _row("rag_off", dt_off * 1e6 / n_docs,
         f"embed_requests={req_off}")
    _row("rag_on", dt_on * 1e6 / n_docs,
         f"embed_requests={req_on} second_query="
         f"{per_query_req[1]} packed_est={packed_est} "
         f"json={out_path.name}")
    return req_off / max(req_on, 1)


def bench_ann():
    """Million-document retrieval (ISSUE 7): IVF-ANN vs the exact scan.

    A 100k-doc clustered synthetic corpus (the geometry real embedding
    corpora exhibit), 64 queries, k=10:

      * exact numpy scan (the ``IVFIndex.exact_scan`` scorer — the same
        arithmetic the IVF path shortcuts to at full probing);
      * Pallas-routed ``topk_sim`` block-max scan (``VectorIndex``
        ``use_kernel=True`` path; interpret-mode on CPU hosts);
      * IVF-ANN at the calibrated nprobe for recall target 0.95.

    Asserts measured recall@10 >= ``BENCH_ANN_RECALL_MIN`` (0.95) and
    IVF speedup over exact >= ``BENCH_ANN_MIN_SPEEDUP`` (5.0 — relaxable
    on oversubscribed CI).  Then the incremental-append contract on a
    provider-backed corpus: growing a built index embeds ONLY the delta
    texts (tuple counts asserted), rows bit-identical to a rebuild.
    """
    from repro.core import MockProvider, SemanticContext
    from repro.retrieval import VectorIndex, ensure_index

    n_docs, dim, n_q, k = 100_000, 64, 64, 10
    rng = np.random.default_rng(0)
    centers = rng.standard_normal((64, dim)).astype(np.float32) * 4.0
    labels = rng.integers(0, 64, n_docs)
    vs = (centers[labels]
          + rng.standard_normal((n_docs, dim)).astype(np.float32))
    qs = vs[rng.integers(0, n_docs, n_q)] + 0.05 * rng.standard_normal(
        (n_q, dim)).astype(np.float32)

    index = VectorIndex(vs)
    qn = qs / np.maximum(np.linalg.norm(qs, axis=1, keepdims=True), 1e-9)
    t0 = time.perf_counter()
    ivf = index.ivf()                          # build + calibrate once
    dt_build = time.perf_counter() - t0
    nprobe = ivf.nprobe_for(0.95)

    dt_exact = _timeit(lambda: ivf.exact_scan(qn, k), n=3, warmup=1)
    dt_kernel = _timeit(
        lambda: VectorIndex(vs, use_kernel=True).topk(qs, k), n=1,
        warmup=1)
    dt_ivf = _timeit(lambda: ivf.search(qn, k, nprobe), n=3, warmup=1)

    _, i_exact = ivf.exact_scan(qn, k)
    _, i_ivf = ivf.search(qn, k, nprobe)
    recall = float(np.mean([len(set(a) & set(b)) / k
                            for a, b in zip(i_ivf, i_exact)]))
    speedup = dt_exact / max(dt_ivf, 1e-9)

    recall_min = float(os.environ.get("BENCH_ANN_RECALL_MIN", "0.95"))
    speedup_min = float(os.environ.get("BENCH_ANN_MIN_SPEEDUP", "5.0"))
    assert recall >= recall_min, \
        f"IVF recall@{k} {recall:.3f} below the {recall_min} gate " \
        f"(nprobe={nprobe}/{ivf.nlist})"
    assert speedup >= speedup_min, \
        f"IVF speedup {speedup:.1f}x below the {speedup_min}x gate " \
        f"({dt_exact*1e3:.1f}ms exact vs {dt_ivf*1e3:.1f}ms IVF)"

    # incremental append: only the delta embeds, rows match a rebuild
    texts = [f"passage {i} body {i % 97}" for i in range(600)]
    emb = {"model": "emb", "embedding_dim": 32, "context_window": 4096}

    def embeds(ctx):
        return sum(r.n_tuples for r in ctx.reports
                   if r.function == "embedding")

    ctx = SemanticContext(provider=MockProvider(), enable_cache=False)
    ensure_index(ctx, emb, texts[:500])
    base_embeds = embeds(ctx)
    t0 = time.perf_counter()
    grown, src = ensure_index(ctx, emb, texts)
    dt_append = time.perf_counter() - t0
    append_embeds = embeds(ctx) - base_embeds
    assert src == "appended" and append_embeds == 100, \
        f"append embedded {append_embeds} tuples (want the 100-delta), " \
        f"source={src}"

    ctx2 = SemanticContext(provider=MockProvider(), enable_cache=False)
    t0 = time.perf_counter()
    rebuilt, _ = ensure_index(ctx2, emb, texts)
    dt_rebuild = time.perf_counter() - t0
    rebuild_embeds = embeds(ctx2)
    assert rebuild_embeds == 600
    assert np.array_equal(grown.raw, rebuilt.raw), \
        "appended index diverges from the from-scratch rebuild"

    results = {
        "docs": n_docs, "dim": dim, "queries": n_q, "k": k,
        "nlist": ivf.nlist, "nprobe": nprobe,
        "recall_at_k": round(recall, 4),
        "exact_scan_ms": round(dt_exact * 1e3, 2),
        "pallas_scan_ms": round(dt_kernel * 1e3, 2),
        "ivf_scan_ms": round(dt_ivf * 1e3, 2),
        "ivf_build_s": round(dt_build, 3),
        "ivf_speedup_vs_exact": round(speedup, 2),
        "append_embedded_tuples": append_embeds,
        "rebuild_embedded_tuples": rebuild_embeds,
        "append_wall_s": round(dt_append, 4),
        "rebuild_wall_s": round(dt_rebuild, 4),
    }
    out_path = Path(__file__).resolve().parent / "BENCH_ann.json"
    out_path.write_text(json.dumps(results, indent=1))

    _row("ann_exact_scan", dt_exact * 1e6 / n_q, f"docs={n_docs}")
    _row("ann_pallas_scan", dt_kernel * 1e6 / n_q, "use_kernel=True")
    _row("ann_ivf_scan", dt_ivf * 1e6 / n_q,
         f"recall@{k}={recall:.3f} nprobe={nprobe}/{ivf.nlist} "
         f"speedup={speedup:.1f}x json={out_path.name}")
    _row("ann_incremental_append", dt_append * 1e6,
         f"delta_tuples={append_embeds} rebuild_tuples={rebuild_embeds}")
    return speedup


def bench_caching():
    from repro.core import MockProvider, SemanticContext, llm_complete
    rows = [{"r": f"text {i}"} for i in range(100)]
    model = {"model": "m", "context_window": 8192, "max_output_tokens": 8}
    ctx = SemanticContext(provider=MockProvider(latency_per_call_s=0.02))
    t_cold = _timeit(lambda: llm_complete(ctx, model, {"prompt": "p"},
                                          rows), n=1, warmup=0)
    t_warm = _timeit(lambda: llm_complete(ctx, model, {"prompt": "p"},
                                          rows), n=1, warmup=0)
    _row("caching_cold", t_cold * 1e6 / len(rows), "cache=miss")
    _row("caching_warm", t_warm * 1e6 / len(rows),
         f"speedup={t_cold/max(t_warm,1e-9):.1f}x "
         f"hits={ctx.cache.stats['hits']}")
    return t_cold / max(t_warm, 1e-9)


def bench_dedup():
    from repro.core import MockProvider, SemanticContext, llm_complete
    rows = [{"city": f"city-{i % 7}"} for i in range(210)]
    model = {"model": "m", "context_window": 600, "max_output_tokens": 8}
    calls = {}
    for on in (False, True):
        prov = MockProvider(latency_per_call_s=0.01)
        ctx = SemanticContext(provider=prov, enable_dedup=on,
                              enable_cache=False)
        llm_complete(ctx, model, {"prompt": "p"}, rows)
        calls[on] = ctx.reports[-1].requests
    _row("dedup", 0.0,
         f"requests_no_dedup={calls[False]} requests_dedup={calls[True]} "
         f"reduction={calls[False]/max(calls[True],1):.0f}x")
    return calls[False] / max(calls[True], 1)


def bench_hybrid_search():
    """Paper Query 3 end-to-end over a synthetic passage corpus."""
    from repro.core import SemanticContext, llm_embedding, llm_rerank, rrf
    from repro.retrieval import BM25Index, VectorIndex
    rng = np.random.default_rng(0)
    vocab = ("join algorithm database query index scan hash sort merge "
             "cyclic vector embedding text search rank").split()
    docs = [" ".join(rng.choice(vocab, 12)) for _ in range(2000)]
    ctx = SemanticContext()
    model = {"model": "e", "embedding_dim": 64}

    def pipeline():
        bm = BM25Index.build(docs)
        b_idx, b_s = bm.topk("cyclic join query", 100)
        vi = VectorIndex(llm_embedding(ctx, model, docs))
        q = llm_embedding(ctx, model, ["cyclic join query"])
        v_s, v_idx = vi.topk(q, 100)
        fb = np.full(len(docs), np.nan)
        fb[b_idx] = b_s
        fv = np.full(len(docs), np.nan)
        fv[v_idx[0]] = v_s[0]
        fused = rrf(fb, fv)
        top10 = np.argsort(-fused)[:10]
        perm = llm_rerank(ctx, {"model": "m"},
                          {"prompt": "mentions cyclic joins"},
                          [{"doc": docs[i]} for i in top10])
        return [int(top10[p]) for p in perm]

    dt = _timeit(pipeline, n=1, warmup=1)
    _row("hybrid_search_q3", dt * 1e6, f"docs={len(docs)} "
         f"rate={len(docs)/dt:.0f}docs/s")


def bench_fusion_methods():
    from repro.core import fusion
    rng = np.random.default_rng(0)
    a, b, c = (rng.random(10_000) for _ in range(3))
    for m in ("rrf", "combsum", "combmnz", "combmed", "combanz"):
        dt = _timeit(lambda m=m: fusion(m, a, b, c), n=5)
        _row(f"fusion_{m}", dt * 1e6, "n=10000x3")


def bench_continuous_batching():
    from repro.configs import get_smoke_config
    from repro.serving.engine import ServingEngine
    cfg = get_smoke_config("olmo-1b").replace(remat=False)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab_size, 24)) for _ in range(8)]

    eng = ServingEngine(cfg, n_slots=4, max_context=128, chunk=16)
    t0 = time.perf_counter()
    reqs = [eng.submit(p, 16) for p in prompts]
    eng.run_until_idle()
    t_cb = time.perf_counter() - t0
    toks = sum(len(r.generated) for r in reqs)

    eng2 = ServingEngine(cfg, n_slots=1, max_context=128, chunk=16)
    t0 = time.perf_counter()
    for p in prompts:
        eng2.generate(p, 16)
    t_seq = time.perf_counter() - t0
    _row("continuous_batching", t_cb * 1e6 / max(toks, 1),
         f"tok/s={toks/t_cb:.1f} vs_sequential={t_seq/t_cb:.2f}x")


def bench_train_step():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.training import HParams, adamw_init, make_train_step
    from repro.training.data import DataConfig, SyntheticTokenPipeline
    cfg = get_smoke_config("olmo-1b").replace(remat=False)
    hp = HParams(total_steps=10)
    step = jax.jit(make_train_step(cfg, hp), donate_argnums=(0, 1))
    data = SyntheticTokenPipeline(DataConfig(cfg.vocab_size, 64, 8))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
    params, opt, _ = step(params, opt, batch)      # compile
    t0 = time.perf_counter()
    for _ in range(5):
        params, opt, m = step(params, opt, batch)
    jax.block_until_ready(m["loss"])
    dt = (time.perf_counter() - t0) / 5
    _row("train_step_smoke", dt * 1e6, f"tok/s={8*64/dt:.0f}")


def bench_kernels():
    import jax.numpy as jnp
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.topk_sim.ops import topk_sim
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 128, 2, 32)), jnp.float32)
    dt = _timeit(lambda: flash_attention(q, k, v, block_q=32, block_k=32
                                         ).block_until_ready(), n=3)
    _row("kernel_flash_attention_interp", dt * 1e6, "B2_S128_H4_hd32")
    dt = _timeit(lambda: attention_ref(q, k, v).block_until_ready(), n=3)
    _row("kernel_flash_attention_ref", dt * 1e6, "oracle")
    c = jnp.asarray(rng.standard_normal((4096, 64)), jnp.float32)
    qs = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    dt = _timeit(lambda: topk_sim(c, qs, 16)[0].block_until_ready(), n=3)
    _row("kernel_topk_sim_interp", dt * 1e6, "N4096_D64_k16")


_ALL_BENCHES = {
    "batching_chat_api": bench_batching_chat_api,
    "optimizer": bench_optimizer,
    "scheduler": bench_scheduler,
    "speculative": bench_speculative,
    "copack": bench_copack,
    "rag": bench_rag,
    "ann": bench_ann,
    "caching": bench_caching,
    "dedup": bench_dedup,
    "fusion_methods": bench_fusion_methods,
    "hybrid_search": bench_hybrid_search,
    "batching_chat_local": bench_batching_chat_local,
    "batching_embedding": bench_batching_embedding,
    "continuous_batching": bench_continuous_batching,
    "train_step": bench_train_step,
    "kernels": bench_kernels,
}


def main(argv: list[str] | None = None) -> None:
    """Run all benches, or only those named on the command line
    (``python benchmarks/run.py scheduler optimizer``)."""
    names = list(argv if argv is not None else sys.argv[1:])
    unknown = [n for n in names if n not in _ALL_BENCHES]
    if unknown:
        raise SystemExit(f"unknown bench(es) {unknown}; "
                         f"choose from {sorted(_ALL_BENCHES)}")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    for name, fn in _ALL_BENCHES.items():
        if not names or name in names:
            fn()


if __name__ == "__main__":
    main()
