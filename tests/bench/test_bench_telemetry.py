"""The program's spans on a recorded profiler trace, and the accepted
readers' inputs left as they were."""

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

    tr = T.flatten(str(tmp_path), spans=T.SPANS + telemetry.SPANS)
    names = [h[0] for h in tr["host"]]
    assert ENGINE <= set(names)
    # one timeline event per span the aggregate counted
    for name in ENGINE:
        assert names.count(name) == after[name]["n"] - before[name]["n"]
    window = next(h for h in tr["host"] if h[0] == "window")
    assert all(window[1] <= s and s + d <= window[1] + window[2]
               for n, s, d in tr["host"] if n in ENGINE)
    assert T.reduce(tr)["window_s"] > 0

    # the accepted reduction keeps the benchmark's own spans alone
    assert {h[0] for h in T.flatten(str(tmp_path))["host"]} == {"window"}


def _rec(tr):
    with open(harness.ROOT / "bench" / "configs" / "olmo-1b.json") as f:
        config = json.load(f)
    return {"trace": T.reduce(tr), "trace_events": tr, "engine_steps": 4,
            "rows": 2, "requests": 1, "retries": 1, "scans": 1,
            "scan_shape": (262144, 2048, 8), "config": config,
            "sequences": [(100, 8), (60, 2)], "embed_lengths": [40, 21],
            "window_s": 2.0, "peak": harness.load_peaks()["TPU v5 lite"]}


# the values these readers returned on this input before the program
# had spans of its own
@pytest.mark.parametrize("name,value", [
    ("device_idle_share", 60.0),
    ("device_ms_per_step", 6.000000000000001e-05),
    ("scan_roofline", 2622160.1758241756),
    ("mfu", 0.12562871956142133),
    ("steps_per_row", 2.0),
    ("requests_per_row", 0.5),
    ("retry_share", 50.0),
])
def test_accepted_readers_read_the_small_trace_as_before(name, value):
    rec = _rec(T.load_json(DATA))
    mod = harness.load_module(harness.ROOT / "bench" / "metrics"
                              / f"{name}.py")
    assert mod.read(rec) == pytest.approx(value, rel=1e-12)
