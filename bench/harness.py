"""One run of one cell: set-up, the measured window, the correctness
check, the metrics.

Everything a cell needs is found by name from ``BENCHMARK.json``:

    configuration   bench/configs/<config>.json   (sizes, limits, the
                    name of its plain reference in bench/reference/)
    traffic         bench/traffic/<traffic>.json  (read by workload.py)
    metric          bench/metrics/<metric>.py     (``read(rec)``)

so a new configuration, traffic mix or metric is a new file and a new
``workloads`` entry, with no edit here.

A configuration file holds ``model``, the block as it is served, in
Hugging Face key names; ``published``, where it is cut from its source,
the published value of each key it cut or departs from (the experts of
the whole model where one chip holds its share), which the FLOP count
and the reference read; and ``smoke_cut``, where its block is not dense:
``{"model": {...}, "published": {...}}``, its keys at the program's
smoke preset, which the CPU tests lay over the dense smoke widths
(``tests/bench/bench_smoke.py``).

The window: plans run back to back from one client (a closed loop)
until ``seconds`` have passed; every plan started is waited for and
counted.  Set-up (imports, the model's weights, the corpus and index,
and warm-up plans that compile every shape the window meets) is timed
as ``setup_s`` and is not in the window.  With ``trace`` the window runs
under the JAX profiler, for at most ``TRACE_WINDOW_S``, and the
per-layer metrics are read from the trace and the counters of that
window; without it, the end-to-end metrics.

A reader gets ``rec``: the window's plans, latencies and counters, the
configuration, the chip's peaks, ``telemetry`` (the program's spans and
counters over the window, ``{name: {"n", "s"}}`` as
``repro.core.telemetry.snapshot()`` names them; ``{}`` where the program
has none), ``n_slots`` (the serving engine's decode slots) and, traced,
``trace_events`` (``bench/trace.py``'s ``flatten``) and ``trace`` (its
``reduce``).
"""

from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
# A traced run measures this much of the window at most: a profiler trace
# of a batch cell holds about 70,000 device events a second, and reading
# it must end well inside a run's time limit.
TRACE_WINDOW_S = 15.0


# ------------------------------------------------------------- discovery
def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(spec: dict, workload: str, root: Path = ROOT) -> dict:
    """The cell's configuration, traffic and metric entries, loaded."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {"name": workload, "chips": int(w["chips"]), "root": root,
            "config": _json(root / conf["file"]),
            "traffic": _json(root / "bench" / "traffic"
                             / f"{w['traffic']}.json"),
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)]}


def read_metrics(entries, rec: dict, root: Path = ROOT) -> dict:
    """{name: {value, unit}} for each metric whose reader finds
    something; a reader that returns None leaves its metric out."""
    out = {}
    for m in entries:
        value = load_module(root / "bench" / "metrics"
                            / f"{m['name']}.py").read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def load_peaks(root: Path = ROOT) -> dict:
    return _json(root / "bench" / "peaks.json")


# ------------------------------------------------------------------- run
def _counters(prov, ctx) -> dict:
    s = prov.stats.snapshot()
    return {"steps": prov.engine.steps, "reports": len(ctx.reports),
            "prompt_tokens": s["prompt_tokens"],
            "output_tokens": s["output_tokens"]}


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def _telemetry() -> dict:
    """The program's span and counter totals since the process started
    (``repro.core.telemetry``), or ``{}`` where the program has none."""
    try:
        from repro.core import telemetry
    except ImportError:
        return {}
    return telemetry.snapshot()


def _since(before: dict, after: dict) -> dict:
    """Per-name difference of two telemetry snapshots."""
    zero = {"n": 0, "s": 0.0}
    return {k: {"n": v["n"] - before.get(k, zero)["n"],
                "s": v["s"] - before.get(k, zero)["s"]}
            for k, v in after.items()}


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_start: float, peak: dict | None = None,
        control: bool = False) -> dict:
    """One run of ``cell``; returns the result line as a dict.  With
    ``control`` the result also holds ``control``: the same comparison
    with the lower-precision reference in the program's place."""
    import jax
    from jax.profiler import TraceAnnotation

    from bench import workload as W
    from bench.provider import TracedProvider
    from repro.core import RequestScheduler, SemanticContext
    from repro.core.cache import corpus_fingerprint
    from repro.engine import Table
    from repro.retrieval import VectorIndex

    config, traffic = cell["config"], cell["traffic"]
    model_seed = int(seed) % 2**32
    models = W.model_specs(config, traffic)
    prov = TracedProvider(config["arch"],
                          use_smoke_config=bool(config.get("smoke", False)),
                          max_context=int(config["max_context"]),
                          seed=model_seed)
    sched = RequestScheduler()
    ctx = SemanticContext(provider=prov, scheduler=sched)

    corpus, doc_id = None, None
    if "corpus" in traffic:
        ctext = W.corpus_texts(traffic, seed)
        dim = int(config["model"]["hidden_size"])
        vectors = np.asarray(W.corpus_vectors(seed, len(ctext), dim))
        index = VectorIndex(vectors)
        del vectors
        index.device_corpus().block_until_ready()
        ctx.store_index((ctx.resolve_model(models["emb"]).ref,
                         corpus_fingerprint(ctext)), index)
        corpus = Table({traffic["corpus"]["col"]: ctext})
        doc_id = {t: i for i, t in enumerate(ctext)}
        del index, ctext

    for which in traffic.get("warmup", ["max"]):
        W.build_plan(ctx, traffic, W.warm_rows(traffic, which, seed),
                     models, corpus).collect()
    jax.effects_barrier()
    setup_s = time.perf_counter() - t_start

    prov.forget()
    before = _counters(prov, ctx)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        seconds = min(seconds, TRACE_WINDOW_S)
        jax.profiler.start_trace(trace_dir)
    plans, latencies, failed = [], [], 0
    telemetry_before = _telemetry()
    with TraceAnnotation("window"):
        t0 = time.perf_counter()
        t_end = t0
        while t_end - t0 < seconds:
            rows = W.plan_rows(traffic, seed, len(latencies))
            t_plan = time.perf_counter()
            try:
                with TraceAnnotation("plan"):
                    out = W.build_plan(ctx, traffic, rows, models,
                                       corpus).collect()
            except Exception:  # noqa: BLE001 - counted and reported
                failed += 1
                print(f"plan {len(latencies)} failed:", file=sys.stderr)
                traceback.print_exc()
                out = None
            t_end = time.perf_counter()
            latencies.append(t_end - t_plan)
            if out is not None:
                plans.append((rows, out))
    telemetry = _since(telemetry_before, _telemetry())
    if trace:
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        print(f"trace: stopped and written in "
              f"{time.perf_counter() - t_stop:.1f} s", file=sys.stderr,
              flush=True)
    after = _counters(prov, ctx)
    devices = jax.local_devices()
    memory_peak = _memory_peak(devices)

    reports = ctx.reports[before["reports"]:after["reports"]]
    rec = {
        "config": config, "traffic": traffic, "peak": peak or {},
        "setup_s": setup_s, "window_s": t_end - t0,
        "latencies": latencies, "plans": len(latencies),
        "rows": sum(int(traffic["rows_per_plan"]) for _ in latencies),
        "requests": sum(r.requests for r in reports),
        "retries": sum(r.retries for r in reports),
        "engine_steps": after["steps"] - before["steps"],
        "prompt_tokens": after["prompt_tokens"] - before["prompt_tokens"],
        "output_tokens": after["output_tokens"] - before["output_tokens"],
        "sequences": [(len(r.prompt), len(r.generated))
                      for r in prov.requests if r.finished],
        "embed_lengths": [len(t.encode()) for texts, _ in prov.embeddings
                          for t in texts],
        "scans": sum(1 for op in traffic["plan"]
                     if op["op"] == "vector_topk") * len(plans),
        "scan_shape": (int(traffic["corpus"]["rows"]),
                       int(config["model"]["hidden_size"]),
                       int(traffic["rows_per_plan"]))
        if "corpus" in traffic else None,
        "telemetry": telemetry, "n_slots": prov.engine.n_slots,
        "trace": None,
    }

    # free the program's state before the reference takes the chip
    recorded = {"embeddings": prov.embeddings, "requests": prov.requests}
    sched.shutdown()
    del prov, ctx, sched, corpus
    gc.collect()
    compiles = sum(v["n"] for k, v in telemetry.items()
                   if k.startswith("compile."))
    print(f"compiles in the window: {compiles}; device bytes in use "
          f"after freeing the program: "
          f"{(devices[0].memory_stats() or {}).get('bytes_in_use')}",
          file=sys.stderr, flush=True)

    checks = check_run(cell, seed, plans, recorded, doc_id, failed)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    result = {"correct": passes(checks), "attempted": len(latencies),
              "failed": failed}
    if trace:
        from bench import trace as T
        t_read = time.perf_counter()
        rec["trace_events"] = T.flatten(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec["trace"] = T.reduce(rec["trace_events"])
        print(f"trace: {sum(map(len, rec['trace_events']['devices'].values()))}"
              f" device events read and reduced in "
              f"{time.perf_counter() - t_read:.1f} s", file=sys.stderr,
              flush=True)
        result["metrics"] = read_metrics(cell["per_layer"], rec,
                                         cell["root"])
        if rec["trace"] is not None:
            device["busy_s"] = rec["trace"]["busy_s"]
            device["window_s"] = rec["trace"]["window_s"]
    else:
        result["metrics"] = read_metrics(cell["end_to_end"], rec,
                                         cell["root"])
    result["device"] = device
    if trace and rec["trace"] is not None:
        from bench import trace as T
        result["breakdown"] = T.breakdown(rec["trace"])
    if control:
        low = check_run(cell, seed, plans, recorded, doc_id, failed, True)
        result["control"] = {"correct": passes(low), "checks": low}
    result["checks"] = checks
    return result


def passes(checks: dict) -> bool:
    return all(c["limit"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


# ----------------------------------------------------------- correctness
def reference_model(config: dict, seed: int, root: Path = ROOT):
    """The configuration's plain reference (``bench/reference``'s
    protocol), built from its ``model`` and ``published`` blocks."""
    mod = load_module(root / "bench" / "reference"
                      / f"{config['reference']}.py")
    return mod.build(config["model"], int(seed) % 2**32,
                     config.get("published", {}))


def check_run(cell: dict, seed: int, plans, recorded: dict, doc_id,
              failed: int, control: bool = False) -> dict:
    """{name: {"value", "limit"}} for every number compared.  With
    ``control`` the int8 reference's tokens and query embeddings, and a
    scan at ``Precision.HIGH``, stand in for what the program served."""
    from bench import check as C

    config, traffic = cell["config"], cell["traffic"]
    ops = {op["op"] for op in traffic["plan"]}
    limits = dict(config.get("limits", {}), **traffic.get("limits", {}))
    checks = {"plans_failed": {"value": failed, "limit": 0}}
    if not plans:
        checks["plans_done"] = {"value": 0, "limit": None}
        return checks
    ref = reference_model(config, seed, cell["root"])
    pad_to = int(config["max_context"])
    if "llm_complete" in ops:
        s = traffic["sample"]
        seqs = C.sample_requests(recorded["requests"], seed,
                                 int(s["max_sequences"]))
        gap = (C.logit_gap(ref, seqs, pad_to, control)
               if seqs else float("inf"))
        checks["logit_gap"] = {"value": gap,
                               "limit": limits.get("logit_gap")}
        checks["rows_wrong"] = {"value": C.rows_wrong(
            traffic, plans, recorded["requests"]), "limit": 0}
    if "vector_topk" in ops:
        checks.update(retrieval_checks(cell, seed, plans, recorded, doc_id,
                                       ref, limits, control))
    return checks


def retrieval_checks(cell, seed, plans, recorded, doc_id, ref, limits,
                     control: bool = False) -> dict:
    """``embed_dist``, ``scan_gap`` and ``rows_wrong`` of a retrieval
    cell."""
    import jax
    import jax.numpy as jnp

    from bench import check as C
    from bench import workload as W

    config, traffic = cell["config"], cell["traffic"]
    op = next(o for o in traffic["plan"] if o["op"] == "vector_topk")
    k, dcol = int(op["k"]), op["doc_col"]
    n_q = int(traffic["sample"]["queries"])
    picks = C.sample_queries(plans, seed, n_q)
    texts = [plans[p][0][j] for p, j in picks]
    served = {}
    for tx, vecs in recorded["embeddings"]:
        served.update(zip(tx, vecs))
    pad = -(-int(traffic["row_bytes"]["max"]) // 32) * 32
    toks, lens = C.embed_rows(texts + [texts[0]] * (n_q - len(texts)), pad)
    ref_q = np.asarray(ref.embed(toks, lens))[:len(texts)]
    if control:
        q = np.asarray(ref.embed(toks, lens, mode="int8"))[:len(texts)]
    else:
        q = np.stack([served.get(t, np.full(ref_q.shape[1], np.nan))
                      for t in texts])
    checks = {"embed_dist": {"value": C.embed_dist(q, ref_q),
                             "limit": limits.get("embed_dist")}}

    n = int(traffic["corpus"]["rows"])
    c = W.corpus_vectors(seed, n, ref_q.shape[1])
    c = c / jnp.maximum(jnp.linalg.norm(c, axis=-1, keepdims=True), 1e-9)
    qd = jnp.asarray(np.nan_to_num(q), jnp.float32)
    qd = qd / jnp.maximum(jnp.linalg.norm(qd, axis=-1, keepdims=True), 1e-9)
    scores = jnp.einsum("qd,nd->qn", qd, c,
                        precision=jax.lax.Precision.HIGHEST)
    if control:
        low = jnp.einsum("qd,nd->qn", qd, c,
                         precision=jax.lax.Precision.HIGH)
        s_srv, i_srv = (np.asarray(a) for a in jax.lax.top_k(low, k))
    else:
        i_srv = np.full((len(picks), k), -1)
        s_srv = np.zeros((len(picks), k))
        for r, (p, j) in enumerate(picks):
            rows, table = plans[p]
            docs = table.column(dcol)[j * k:(j + 1) * k]
            sc = table.column(op["out"])[j * k:(j + 1) * k]
            i_srv[r, :len(docs)] = [doc_id.get(d, -1) for d in docs]
            s_srv[r, :len(sc)] = sc
    checks["scan_gap"] = {"value": C.scan_gap(np.asarray(scores), i_srv,
                                              s_srv, k),
                          "limit": limits.get("scan_gap")}
    del c, scores
    checks["rows_wrong"] = {"value": C.retrieval_rows_wrong(
        traffic, plans, doc_id), "limit": 0}
    return checks
