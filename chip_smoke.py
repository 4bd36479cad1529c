#!/usr/bin/env python3
"""Bring-up check: the served path on a TPU, through the library's own
entry points, with olmo-1b at its published width (random weights from
``--seed``; no checkpoint ships with the repository).

    python chip_smoke.py [--seed 0]             # one chip, every phase
    python chip_smoke.py --chips 4 [--seed 0]   # the sharded scan only

One chip:

  semantic   Pipeline ``llm_filter -> llm_complete`` over generated rows:
             ``collect()`` -> optimizer -> RequestScheduler ->
             LocalJaxProvider -> ServingEngine (chunked prefill, decode).
  retrieval  ``hybrid_topk -> llm_rerank`` over a corpus the same engine
             embeds; then ``VectorIndex.topk`` over a 262,144 x 2048
             float32 corpus (2 GiB, the scan a deployment keeps on one
             chip) against ``cosine_topk`` on the same chip, with the scan
             compiled as a ``tpu_custom_call``.
  model      the engine's teacher-forced logits of a 256-token prompt
             (its compiled chunked-prefill and decode steps) against a
             float32 forward of the same parameters.

Four chips (``--chips 4``): ``VectorIndex(..., mesh=...)`` over a
2,097,152 x 2048 float32 corpus (16 GiB, more than one chip holds), one
quarter placed on each chip, against a per-shard ``einsum`` + ``top_k``
reference merged in NumPy.

Every phase prints its checks.  The script exits non-zero, and prints no
result, unless JAX's platform is ``tpu`` and every phase passes.  The last
line of standard output is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "olmo-1b"
# bf16 serving vs a float32 forward of the same weights.  The logits are
# unit-variance (std 1.0, |max| about 5.5), and the engine stores them
# and every activation in bf16, whose spacing at |x| in [4, 8) is 1/32.
# Full width on the CPU measured 0.059 (2 layers) and 0.067 (4 layers);
# 0.25 is eight bf16 spacings at the largest logits, while a wrong cache
# slot, position or mask moves logits by O(1).
LOGIT_TOL = 0.25
SCORE_TOL = 1e-5       # float32 cosine scores, both sides at fp32 contract


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def log(phase: str, msg: str):
    print(f"[{phase}] {msg}", flush=True)


def random_corpus(seed: int, n: int, d: int) -> np.ndarray:
    """(n, d) float32 standard normal rows from ``seed``, filled by
    threads over row blocks (numpy releases the GIL while it fills)."""
    out = np.empty((n, d), np.float32)
    parts = 16
    seeds = np.random.SeedSequence(seed).spawn(parts)

    def fill(i):
        rows = slice(i * n // parts, (i + 1) * n // parts)
        np.random.default_rng(seeds[i]).standard_normal(
            out=out[rows], dtype=np.float32)

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(fill, range(parts)))
    return out


def planted_queries(corpus: np.ndarray, seed: int, n_q: int):
    """``n_q`` queries, each a corpus row plus noise: its own row is the
    clear top-1.  Returns (queries, planted row ids)."""
    rng = np.random.default_rng(seed + 1)
    rows = rng.choice(len(corpus), n_q, replace=False)
    q = corpus[rows] + 0.5 * rng.standard_normal(
        (n_q, corpus.shape[1])).astype(np.float32)
    return q.astype(np.float32), rows


def compare_topk(phase, s, i, s_ref, i_ref, rows):
    for r in range(len(i)):
        check(set(i[r].tolist()) == set(i_ref[r].tolist()),
              f"query {r}: index sets differ: {i[r]} vs {i_ref[r]}")
    err = float(np.abs(np.sort(s, 1) - np.sort(s_ref, 1)).max())
    log(phase, f"index sets equal for {len(i)} queries; "
               f"max |score - reference| = {err:.3e} (tol {SCORE_TOL})")
    check(err <= SCORE_TOL, f"score error {err} > {SCORE_TOL}")
    check((i[:, 0] == rows).all(), f"planted top-1 missed: {i[:, 0]} "
                                   f"vs {rows}")


@contextmanager
def engine_requests(engine):
    """While the block runs, record every generation request and every
    embedding batch the engine is given (the scheduler's worker threads
    reach it through ``generate`` and ``embed_batch``)."""
    seen = {"requests": [], "embeds": 0}
    submit, embed_batch = engine.submit, engine.embed_batch

    def recording_submit(*a, **kw):
        req = submit(*a, **kw)
        seen["requests"].append(req)
        return req

    def recording_embed_batch(*a, **kw):
        seen["embeds"] += 1
        return embed_batch(*a, **kw)

    engine.submit = recording_submit
    engine.embed_batch = recording_embed_batch
    try:
        yield seen
    finally:
        del engine.submit, engine.embed_batch


def check_requests(phase, prov, seen, calls_before):
    calls = prov.stats.snapshot()["calls"] - calls_before
    reqs = seen["requests"]
    check(reqs, "no generation request reached the engine")
    check(len(reqs) + seen["embeds"] == calls,
          f"{calls} provider requests but {len(reqs)} generation requests "
          f"and {seen['embeds']} embedding batches reached the engine")
    check(all(r.finished and r.generated for r in reqs),
          "a request finished without generating a token")
    log(phase, f"{calls} provider requests, all reached the engine "
               f"({seen['embeds']} embedding batches); tokens per "
               f"generation request {[len(r.generated) for r in reqs]}")


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
def phase_semantic(prov, seed: int, n_rows: int = 48):
    from repro.core import RequestScheduler, SemanticContext
    from repro.engine import Pipeline, Table

    rng = np.random.default_rng(seed)
    things = ["transfer", "login", "card", "statement", "app", "loan"]
    events = ["failed", "crashed", "worked", "was slow", "timed out"]
    table = Table({"id": list(range(n_rows)), "review": [
        f"review {i}: the {rng.choice(things)} {rng.choice(events)} "
        f"{int(rng.integers(1, 9))} times this week" for i in range(n_rows)]})
    model = {"model": "olmo-1b-local", "context_window": 2048,
             "max_output_tokens": 4}
    steps0, calls0 = prov.engine.steps, prov.stats.snapshot()["calls"]
    with engine_requests(prov.engine) as seen, RequestScheduler() as sched:
        ctx = SemanticContext(provider=prov, scheduler=sched)
        pipe = (Pipeline(ctx, table, "reviews")
                .llm_filter(model, {"prompt": "mentions technical issues"},
                            ["review"])
                .llm_complete("severity", model,
                              {"prompt": "assign a severity 1-5"},
                              ["review"]))
        out = pipe.collect()
    check("severity" in out.column_names, "plan lost its output column")
    check(prov.engine.steps > steps0, "engine took no step")
    check_requests("semantic", prov, seen, calls0)
    log("semantic", f"{n_rows} rows -> {len(out)} rows out; engine steps "
                    f"{prov.engine.steps - steps0}; reports "
                    f"{[(r.function, r.requests, r.retries) for r in ctx.reports]}")


PASSAGES = [
    "hash joins build a table then probe it",
    "sort merge joins exploit interesting orders",
    "worst case optimal joins handle cyclic join queries",
    "b trees remain the default index structure",
    "vector search scans embeddings for nearest neighbours",
    "query optimizers reorder joins by cost",
    "columnar storage accelerates analytical scans",
    "bm25 ranks documents by term frequency saturation",
]


def phase_retrieval_plan(prov, n_passages: int = 64):
    from repro.core import RequestScheduler, SemanticContext
    from repro.engine import Pipeline, Table

    corpus = Table({"content": [
        f"passage {i}: {PASSAGES[i % len(PASSAGES)]}"
        for i in range(n_passages)]})
    questions = Table({"q": ["cyclic join algorithms",
                             "nearest neighbour vector search"]})
    emb = {"model": "olmo-1b-embed", "context_window": 2048}
    model = {"model": "olmo-1b-local", "context_window": 2048,
             "max_output_tokens": 4}
    calls0 = prov.stats.snapshot()["calls"]
    with engine_requests(prov.engine) as seen, RequestScheduler() as sched:
        ctx = SemanticContext(provider=prov, scheduler=sched)
        pipe = (Pipeline(ctx, questions, "questions")
                .hybrid_topk("score", emb, "q", corpus, k=5,
                             doc_col="content", candidate_k=10)
                .llm_rerank(model, {"prompt": "mentions joins"},
                            ["content"], by="q"))
        pipe.check()
        out = pipe.collect()
    check(len(out) == 2 * 5, f"expected 10 rows, got {len(out)}")
    check(out.column("q") == ["cyclic join algorithms"] * 5
          + ["nearest neighbour vector search"] * 5,
          "rerank broke the per-question groups")
    embeds = [r for r in ctx.reports if r.function == "embedding"]
    check(sum(r.n_tuples for r in embeds) >= n_passages,
          "the corpus was not embedded by the engine")
    check_requests("retrieval", prov, seen, calls0)
    log("retrieval", f"hybrid_topk -> llm_rerank: {len(out)} rows; "
                     f"{len(embeds)} embed dispatches over "
                     f"{sum(r.n_tuples for r in embeds)} texts")


def phase_scan(seed: int, n: int = 262_144, d: int = 2048, n_q: int = 8,
               k: int = 10):
    import jax
    import jax.numpy as jnp
    from repro.kernels import resolve_interpret
    from repro.kernels.topk_sim.ops import topk_sim
    from repro.retrieval import VectorIndex, cosine_topk

    t0 = time.perf_counter()
    corpus = random_corpus(seed, n, d)
    q, rows = planted_queries(corpus, seed, n_q)
    index = VectorIndex(corpus)
    del corpus
    dev = index.device_corpus()
    dev.block_until_ready()
    log("scan", f"corpus {n} x {d} float32 ({dev.nbytes / 2**30:.2f} GiB) "
                f"on {dev.devices()}; set-up {time.perf_counter() - t0:.1f} s")

    s, i = index.topk(q, k)                     # VectorIndex's own route
    qd = jnp.asarray(q)
    ref = jax.jit(cosine_topk, static_argnames=("k",))
    s_ref, i_ref = (np.asarray(a) for a in ref(dev, qd, k=k))
    compare_topk("scan", s, i, s_ref, i_ref, rows)

    for name, fn in (("topk_sim", lambda: topk_sim(dev, qd, k)),
                     ("cosine_topk", lambda: ref(dev, qd, k=k))):
        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        log("scan", f"{name} warm call: {time.perf_counter() - t0:.6f} s "
                    "(host clock, one call)")

    hlo = topk_sim.lower(dev, qd, k).compile().as_text()
    check(not resolve_interpret(None), "kernels resolve to interpret mode")
    check("tpu_custom_call" in hlo,
          "topk_sim did not compile to a tpu_custom_call")
    log("scan", "topk_sim compiled as a tpu_custom_call (not interpreted)")


def phase_model(prov, seed: int, n_tokens: int = 256):
    import jax
    import jax.numpy as jnp
    from repro.models import model as M

    eng = prov.engine
    rng = np.random.default_rng(seed + 2)
    toks = [int(t) for t in rng.integers(0, 256, n_tokens)]  # byte tokens
    got = eng.score(toks)
    cfg32 = eng.cfg.replace(param_dtype="float32", compute_dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), eng.params)
    fwd = jax.jit(lambda p, t: M.forward_train(cfg32, p, {"tokens": t})[0])
    with jax.default_matmul_precision("highest"):
        ref = fwd(p32, jnp.asarray([toks], jnp.int32))
    ref = np.asarray(ref[0, :, :eng.cfg.vocab_size])
    del p32
    err = np.abs(got - ref)
    check(np.isfinite(got).all(), "non-finite engine logits")
    log("model", f"{n_tokens} tokens ({(n_tokens - 1) // eng.chunk} "
                 f"prefill chunks of {eng.chunk}, the rest decoded): "
                 f"max |logit - f32 reference| = {err.max():.4f} "
                 f"(tol {LOGIT_TOL}), mean {err.mean():.5f}, "
                 f"|ref| max {np.abs(ref).max():.3f}, argmax agreement "
                 f"{(got.argmax(-1) == ref.argmax(-1)).mean():.4f}")
    check(err.max() <= LOGIT_TOL,
          f"logit error {err.max()} > tolerance {LOGIT_TOL}")


def phase_sharded(seed: int, chips: int, n: int = 2_097_152, d: int = 2048,
                  n_q: int = 8, k: int = 10):
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.retrieval import VectorIndex

    mesh = make_mesh((chips,), ("data",))
    t0 = time.perf_counter()
    corpus = random_corpus(seed, n, d)
    q, rows = planted_queries(corpus, seed, n_q)
    index = VectorIndex(corpus, mesh=mesh)
    del corpus
    dev = index.device_corpus(mesh)
    dev.block_until_ready()
    shards = dev.addressable_shards
    log("sharded", f"corpus {n} x {d} float32 ({dev.nbytes / 2**30:.2f} "
                   f"GiB) over {chips} chips; set-up "
                   f"{time.perf_counter() - t0:.1f} s")
    check(len({sh.device for sh in shards}) == chips == len(shards),
          "corpus is not one shard per chip")
    check(all(sh.data.shape == (n // chips, d) for sh in shards),
          f"shard shapes {[sh.data.shape for sh in shards]}")
    starts = [sh.index[0].start or 0 for sh in shards]
    log("sharded", "each chip holds a quarter: " + ", ".join(
        f"device {sh.device.id} rows [{lo}, {lo + n // chips})"
        for sh, lo in zip(shards, starts)))

    s, i = index.topk(q, k)

    @jax.jit
    def shard_topk(c, qs):
        qn = qs / jnp.linalg.norm(qs, axis=-1, keepdims=True)
        sc = jnp.einsum("qd,nd->qn", qn, c,
                        precision=jax.lax.Precision.HIGHEST)
        return jax.lax.top_k(sc, k)

    cand_s, cand_i = [], []
    for sh, lo in zip(shards, starts):
        ss, ii = shard_topk(sh.data, jax.device_put(q, sh.device))
        cand_s.append(np.asarray(ss))
        cand_i.append(np.asarray(ii) + lo)
    cand_s, cand_i = np.concatenate(cand_s, 1), np.concatenate(cand_i, 1)
    order = np.argsort(-cand_s, axis=1, kind="stable")[:, :k]
    s_ref = np.take_along_axis(cand_s, order, 1)
    i_ref = np.take_along_axis(cand_i, order, 1)
    compare_topk("sharded", s, i, s_ref, i_ref, rows)


# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded corpus scan on 4 chips")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"no repro package under {ROOT / 'src'}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"devices: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; compile cache {cache_dir}", flush=True)
    if dev.platform != "tpu":
        print(f"platform is {dev.platform!r}, not 'tpu'", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 1

    if args.chips == 4:
        phases = [("sharded", lambda: phase_sharded(args.seed, 4))]
    else:
        from repro.core.provider import LocalJaxProvider
        prov = None

        def build():
            nonlocal prov
            prov = LocalJaxProvider(ARCH, use_smoke_config=False,
                                    seed=args.seed)
            c = prov.engine.cfg
            log("build", f"{c.name}: {c.num_layers} layers, d_model "
                         f"{c.d_model}, {c.num_heads} heads of "
                         f"{c.resolved_head_dim}, d_ff {c.d_ff}, vocab "
                         f"{c.vocab_size}, {c.param_dtype}; "
                         f"{prov.engine.n_slots} slots x "
                         f"{prov.engine.max_context} context")

        phases = [("build", build),
                  ("semantic", lambda: phase_semantic(prov, args.seed)),
                  ("retrieval", lambda: phase_retrieval_plan(prov)),
                  ("scan", lambda: phase_scan(args.seed)),
                  ("model", lambda: phase_model(prov, args.seed))]

    results = {}
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
            results[name] = True
        except Exception as e:  # noqa: BLE001 — report every phase
            results[name] = False
            log(name, f"FAIL {type(e).__name__}: {e}")
            if name == "build":
                break
        log(name, f"{'pass' if results[name] else 'FAIL'} in "
                  f"{time.perf_counter() - t0:.1f} s (host clock)")
    print("phases: " + ", ".join(
        f"{k}={'pass' if v else 'FAIL'}" for k, v in results.items()),
        flush=True)
    if len(results) != len(phases) or not all(results.values()):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
