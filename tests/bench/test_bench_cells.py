"""Every configuration, traffic mix and metric file, run at smoke width on
the CPU through the harness's own functions."""

import json
import math
import time

import pytest

from bench import harness

from bench_smoke import WAITING

SPEC = harness.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]] + sorted(WAITING)
PEAK = harness.load_peaks()["TPU v5 lite"]


def run(cell, trace, seed=2**31 + 17, seconds=0.05):
    return harness.run(cell, seed, seconds, trace, time.perf_counter(), PEAK)


def test_every_file_belongs_to_a_cell():
    root = harness.ROOT / "bench"
    traffic = {w["traffic"] for w in SPEC["workloads"]} | {
        t for _, _, t in WAITING.values()}
    configs = {c["file"] for c in SPEC["configs"]} | {
        f"bench/configs/{c}.json" for _, c, _ in WAITING.values()}
    metrics = {m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]}
    assert {f"bench/configs/{p.name}" for p in (root / "configs").glob(
        "*.json")} == configs
    assert {p.stem for p in (root / "traffic").glob("*.json")} == traffic
    assert {p.stem for p in (root / "metrics").glob("*.py")} == metrics


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_and_is_correct_at_smoke_width(smoke_cell, name, trace):
    cell = smoke_cell(name)
    res = run(cell, trace)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    for c in res["checks"].values():
        assert c["limit"] is not None and c["value"] <= c["limit"]
    want = cell["per_layer"] if trace else cell["end_to_end"]
    # device readings need the chip; every other metric reads here
    on_cpu = {m["name"] for m in want if m["source"] != "device_trace"}
    assert on_cpu <= set(res["metrics"])
    for m in want:
        if m["name"] in res["metrics"]:
            got = res["metrics"][m["name"]]
            assert got["unit"] == m["unit"] and math.isfinite(got["value"])
    if trace:
        assert "breakdown" in res and res["device"]["window_s"] > 0
    json.dumps(res)


def test_a_token_altered_where_it_is_produced_fails_the_check(
        smoke_cell, monkeypatch):
    from repro.serving import engine as E

    step = E.ServingEngine.step

    def altered(self):
        before = {id(r): len(r.generated) for r in self.active if r}
        step(self)
        for r in self.active:
            if r and len(r.generated) > before.get(id(r), 0):
                tok = (r.generated[-1] + 1) % self.cfg.vocab_size
                r.generated[-1] = tok
                self.cur_tok[r.slot] = tok

    monkeypatch.setattr(E.ServingEngine, "step", altered)
    res = run(smoke_cell("olmo-1b.batch_map"), False)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_a_candidate_altered_where_it_is_produced_fails_the_check(
        smoke_cell, monkeypatch):
    from repro.retrieval import vector as V

    topk = V.VectorIndex.topk

    def altered(self, q, k=100):
        s, i = topk(self, q, k)
        return s, (i + 1) % len(self.vectors)

    monkeypatch.setattr(V.VectorIndex, "topk", altered)
    res = run(smoke_cell("olmo-1b.rag_scan"), False)
    assert not res["correct"]
    assert res["checks"]["scan_gap"]["value"] > \
        res["checks"]["scan_gap"]["limit"]


def test_a_row_dropped_from_a_plan_fails_the_check(smoke_cell, monkeypatch):
    from repro.engine import pipeline as P

    collect = P.Pipeline.collect

    def dropped(self, *a, **kw):
        out = collect(self, *a, **kw)
        return out.limit(len(out) - 1)

    monkeypatch.setattr(P.Pipeline, "collect", dropped)
    res = run(smoke_cell("olmo-1b.batch_map"), False)
    assert not res["correct"] and res["checks"]["rows_wrong"]["value"] > 0


def test_an_answer_moved_between_requests_fails_the_check(smoke_cell,
                                                          monkeypatch):
    from repro.core import provider as P

    complete = P.LocalJaxProvider.complete
    last = {}

    def moved(self, model, mp, n_rows):
        lines = complete(self, model, mp, n_rows)
        prev, last["lines"] = last.get("lines"), lines
        if prev is None:
            return lines
        # this request's rows get the previous request's answers
        return [f"{i}: {prev[0].split(': ', 1)[1]}" for i in range(n_rows)]

    monkeypatch.setattr(P.LocalJaxProvider, "complete", moved)
    res = run(smoke_cell("olmo-1b.batch_map"), False)
    assert not res["correct"] and res["checks"]["rows_wrong"]["value"] > 0


def test_a_row_sent_twice_fails_the_check(smoke_cell, monkeypatch):
    from repro.core import provider as P

    complete = P.LocalJaxProvider.complete

    def twice(self, model, mp, n_rows):
        lines = complete(self, model, mp, n_rows)
        complete(self, model, mp, n_rows)      # the same rows served again
        return lines

    monkeypatch.setattr(P.LocalJaxProvider, "complete", twice)
    res = run(smoke_cell("olmo-1b.batch_map"), False)
    assert not res["correct"] and res["checks"]["rows_wrong"]["value"] > 0
