"""The program's spans on a recorded profiler trace, the readers of its
window telemetry, and the accepted readers' inputs left as they were."""

import json
import os

import pytest

from bench import harness
from bench import trace as T
from repro.core import telemetry

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")
ENGINE = {"engine.admit", "engine.prefill", "engine.decode",
          "engine.sample"}


def test_the_programs_spans_land_on_the_profilers_timeline(tmp_path):
    import jax

    from repro.configs import get_smoke_config
    from repro.serving.engine import ServingEngine

    telemetry.install()
    cfg = get_smoke_config("olmo-1b").replace(remat=False)
    eng = ServingEngine(cfg, n_slots=2, max_context=48, chunk=8, seed=0)
    eng.generate(list(range(20)), 3)            # compiles
    before = telemetry.snapshot()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        eng.generate(list(range(20)), 3)
    jax.profiler.stop_trace()
    after = telemetry.snapshot()

    tr = T.flatten(str(tmp_path))
    names = [h[0] for h in tr["program"]]
    assert ENGINE <= set(names) <= set(telemetry.SPANS)
    # one timeline event per span the aggregate counted
    for name in ENGINE:
        assert names.count(name) == after[name]["n"] - before[name]["n"]
    window = next(h for h in tr["host"] if h[0] == "window")
    assert all(window[1] <= s and s + d <= window[1] + window[2]
               for n, s, d in tr["program"] if n in ENGINE)
    assert T.reduce(tr)["window_s"] > 0

    # the accepted reduction keeps the benchmark's own spans alone
    assert {h[0] for h in tr["host"]} == {"window"}


# the program's spans over trace_small.json's window, one of them open
# across each idle gap the benchmark's spans label
PROGRAM = [["engine.decode", 100, 30], ["engine.sample", 170, 130],
           ["scheduler.dispatch", 60, 600], ["engine.admit", 415, 85]]
TELEMETRY = {"engine.decode": {"n": 10, "s": 0.5},
             "engine.slot_steps": {"n": 10, "s": 0.0},
             "engine.sample": {"n": 10, "s": 0.2},
             "compile.engine_decode": {"n": 0, "s": 0.0}}


def _rec(tr, telemetry=None):
    with open(harness.ROOT / "bench" / "configs" / "olmo-1b.json") as f:
        config = json.load(f)
    return {"trace": T.reduce(tr), "trace_events": tr, "engine_steps": 4,
            "rows": 2, "requests": 1, "retries": 1, "scans": 1,
            "scan_shape": (262144, 2048, 8), "config": config,
            "sequences": [(100, 8), (60, 2)], "embed_lengths": [40, 21],
            "window_s": 2.0, "peak": harness.load_peaks()["TPU v5 lite"],
            "telemetry": TELEMETRY if telemetry is None else telemetry,
            "n_slots": 4}


def read(name, rec):
    return harness.load_module(harness.ROOT / "bench" / "metrics"
                               / f"{name}.py").read(rec)


# the values these readers returned on this input before the program
# had spans of its own
ACCEPTED = pytest.mark.parametrize("name,value", [
    ("device_idle_share", 60.0),
    ("device_ms_per_step", 6.000000000000001e-05),
    ("scan_roofline", 2622160.1758241756),
    ("mfu", 0.12562871956142133),
    ("steps_per_row", 2.0),
    ("requests_per_row", 0.5),
    ("retry_share", 50.0),
])


@ACCEPTED
def test_accepted_readers_read_the_small_trace_as_before(name, value):
    rec = _rec(T.load_json(DATA))
    assert read(name, rec) == pytest.approx(value, rel=1e-12)


@ACCEPTED
def test_accepted_readers_read_the_same_beside_program_spans(name, value):
    tr = T.load_json(DATA)
    tr["program"] = PROGRAM
    assert read(name, _rec(tr)) == pytest.approx(value, rel=1e-12)


def test_program_spans_leave_the_reduction_as_it_was():
    tr = T.load_json(DATA)
    red = T.reduce(tr)
    tr["program"] = PROGRAM
    assert T.reduce(tr) == red
    assert T.breakdown(T.reduce(tr)) == T.breakdown(red)
    assert {h[0] for h in tr["host"]} <= set(T.SPANS)
    assert not {h[0] for h in tr["host"]} & set(telemetry.SPANS)


@pytest.mark.parametrize("name,tel,value", [
    # 10 decode steps with one of 4 slots active each
    ("slot_fill", TELEMETRY, 25.0),
    ("slot_fill", dict(TELEMETRY, **{"engine.slot_steps": {"n": 40,
                                                           "s": 0.0}}),
     100.0),
    # rag: embeddings only, no decode step to fill
    ("slot_fill", {"engine.embed": {"n": 3, "s": 0.1}}, None),
    ("slot_fill", {}, None),
    ("window_compiles", TELEMETRY, 0),
    ("window_compiles", dict(TELEMETRY, **{
        "compile.engine_decode": {"n": 2, "s": 0.0},
        "compile.topk_sim": {"n": 1, "s": 0.0}}), 3),
    ("window_compiles", {}, None),
])
def test_program_counter_readers(name, tel, value):
    assert read(name, _rec(T.load_json(DATA), tel)) == value


def test_mfu_counts_a_moe_block_by_its_architecture():
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "moonlight_block.json")) as f:
        block = json.load(f)
    rec = _rec(T.load_json(DATA))
    rec["config"] = {"model": block["model"],
                     "published": block["published"]}
    # positions run: 100 + 7 and 60 + 1 of the two requests, 40 and 21
    # embedded; 2,125,463,552 weight FLOPs a position, 27 layers of
    # 10,240 attention FLOPs a cached position, 2 * 2048 * 163840 a
    # served token's logits (10 served)
    lengths = [107, 61, 40, 21]
    work = (2_125_463_552 * sum(lengths)
            + 27 * 10_240 * sum(n * (n + 1) // 2 for n in lengths)
            + 2 * 2048 * 163840 * 10)
    assert read("mfu", rec) == pytest.approx(
        100.0 * work / (2.0 * 197e12), rel=1e-12)
    # the same block with all 64 experts counted as held here reads more
    rec["config"]["published"] = {}
    rec["config"]["model"] = dict(block["model"], n_routed_experts=64)
    assert read("mfu", rec) > 100.0 * work / (2.0 * 197e12)
