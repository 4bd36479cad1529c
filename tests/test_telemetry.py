"""The program's spans and counters (``repro.core.telemetry``)."""

import gc
import os
import re
import subprocess
import sys
import threading
import time

import pytest

from repro.core import telemetry

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def delta(before, after=None):
    after = telemetry.snapshot() if after is None else after
    out = {}
    for k, v in after.items():
        b = before.get(k, {"n": 0, "s": 0.0})
        if v["n"] != b["n"]:
            out[k] = {"n": v["n"] - b["n"], "s": v["s"] - b["s"]}
    return out


def test_spans_add_their_count_and_seconds():
    before = telemetry.snapshot()
    for _ in range(3):
        with telemetry.span("test.sleep"):
            time.sleep(0.01)
    telemetry.count("test.items", 5)
    telemetry.count("test.items")
    d = delta(before)
    assert d["test.sleep"]["n"] == 3
    assert 0.03 <= d["test.sleep"]["s"] < 1.0
    assert d["test.items"] == {"n": 6, "s": 0.0}


def test_spans_nest_and_a_span_inside_its_own_name_counts_once():
    before = telemetry.snapshot()
    with telemetry.span("test.outer"):
        with telemetry.span("test.inner"):
            time.sleep(0.01)
        with telemetry.span("test.outer"):
            time.sleep(0.01)
    d = delta(before)
    assert d["test.inner"]["n"] == 1 and d["test.outer"]["n"] == 1
    assert d["test.outer"]["s"] >= d["test.inner"]["s"] + 0.01
    # the same name opens again once the outer span has closed
    with telemetry.span("test.outer"):
        pass
    assert delta(before)["test.outer"]["n"] == 2


def test_a_span_records_when_its_body_raises():
    before = telemetry.snapshot()
    with pytest.raises(ValueError):
        with telemetry.span("test.raises"):
            raise ValueError
    assert delta(before)["test.raises"]["n"] == 1
    with telemetry.span("test.raises"):     # not left open
        pass
    assert delta(before)["test.raises"]["n"] == 2


def test_spans_and_counters_from_eight_threads_add_up():
    before = telemetry.snapshot()
    start = threading.Barrier(8)

    def work():
        start.wait()
        for _ in range(500):
            with telemetry.span("test.threads"):
                telemetry.count("test.thread_items", 2)

    threads = [threading.Thread(target=work) for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)         # switch threads mid-update
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    d = delta(before)
    assert d["test.threads"]["n"] == 8 * 500
    assert d["test.thread_items"]["n"] == 8 * 500 * 2


def test_snapshots_only_grow():
    a = telemetry.snapshot()
    with telemetry.span("test.grow"):
        pass
    b = telemetry.snapshot()
    assert all(b[k]["n"] >= v["n"] and b[k]["s"] >= v["s"]
               for k, v in a.items())


def test_the_compile_counter_names_the_jitted_function():
    import jax
    import jax.numpy as jnp

    telemetry.install()
    telemetry.install()                 # idempotent: one listener

    def telemetry_probe(x):
        return x * 3 + 1

    f = jax.jit(telemetry_probe)
    before = telemetry.snapshot()
    f(jnp.ones(5)).block_until_ready()
    f(jnp.ones(5)).block_until_ready()
    d = delta(before)
    assert d["compile.telemetry_probe"]["n"] == 1
    f(jnp.ones(6)).block_until_ready()
    assert delta(before)["compile.telemetry_probe"]["n"] == 2


def test_a_garbage_collection_is_a_python_gc_span():
    telemetry.install()
    before = telemetry.snapshot()
    gc.collect()
    d = delta(before)
    assert d["python.gc"]["n"] >= 1 and d["python.gc"]["s"] > 0


def _engine(**kw):
    from repro.configs import get_smoke_config
    from repro.serving.engine import ServingEngine

    cfg = get_smoke_config("olmo-1b").replace(remat=False)
    return ServingEngine(cfg, **dict(dict(n_slots=3, max_context=48,
                                          chunk=8, seed=0), **kw))


def test_engine_counts_slot_fill_and_the_steps_that_did_work(rng):
    telemetry.install()
    eng = _engine()
    vocab = eng.cfg.vocab_size
    before = telemetry.snapshot()
    # two prompts of one length: each prefills its chunks alone, then
    # both decode in every shared step and finish together
    for _ in range(2):
        eng.submit(list(rng.integers(0, vocab, 21)), 4)
    eng.run_until_idle()
    d = delta(before)
    decodes, prefills = d["engine.decode"]["n"], d["engine.prefill"]["n"]
    assert prefills == 2 * (20 // 8)
    assert d["engine.slot_steps"]["n"] == 2 * decodes
    assert decodes + prefills == eng.steps
    assert d["engine.admit"]["n"] == eng.steps
    assert d["engine.sample"]["n"] == decodes
    # each engine step is compiled under its own name
    assert {"compile.engine_decode", "compile.engine_prefill",
            "compile.engine_merge_row"} <= set(d)

    # one request alone fills one slot of the three
    before = telemetry.snapshot()
    eng.generate(list(rng.integers(0, vocab, 5)), 3)
    d = delta(before)
    assert d["engine.slot_steps"]["n"] == d["engine.decode"]["n"]
    assert "engine.prefill" not in d


def test_engine_embeds_under_one_name_for_every_length():
    telemetry.install()
    eng = _engine()
    before = telemetry.snapshot()
    eng.embed_batch([[1, 2, 3]])
    eng.embed_batch([list(range(40))])
    d = delta(before)
    assert d["engine.embed"]["n"] == 2
    assert d["compile.engine_embed"]["n"] == 2      # two length buckets


def test_the_provider_records_the_wait_for_its_engine(monkeypatch):
    from repro.core.metaprompt import build_metaprompt
    from repro.core.provider import LocalJaxProvider
    from repro.core.resources import ModelResource

    prov = LocalJaxProvider("olmo-1b", max_context=1024)
    model = ModelResource(name="local", version=1, arch="olmo-1b",
                          context_window=4096, max_output_tokens=2)
    mp = build_metaprompt("complete", "echo", [{"t": "x"}], "xml")

    def slow_generate(prompt, max_new_tokens=32, eos_token=-1):
        time.sleep(0.3)
        return [65] * max_new_tokens

    monkeypatch.setattr(prov.engine, "generate", slow_generate)
    start = threading.Barrier(2)

    def call():
        start.wait()
        prov.complete(model, mp, 1)

    before = telemetry.snapshot()
    threads = [threading.Thread(target=call) for _ in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    d = delta(before)["provider.engine_wait"]
    # one call found the engine free; the other waited out its request
    assert d["n"] == 2 and 0.2 <= d["s"] < 2.0


def _span_names_in_src():
    names = set()
    for root, _, files in os.walk(SRC):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    names |= set(re.findall(r'telemetry\.span\("([^"]+)"\)',
                                            fh.read()))
    return names


def test_spans_lists_every_span_the_program_opens():
    opened = _span_names_in_src()
    assert opened == set(telemetry.SPANS) - {"python.gc"}
    assert set(telemetry.WAITS) <= set(telemetry.SPANS)


def test_only_the_telemetry_module_imports_the_profiler():
    users = []
    for root, _, files in os.walk(SRC):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    if "jax.profiler" in fh.read():
                        users.append(f)
    assert users == ["telemetry.py"]


def test_importing_the_core_loads_no_jax():
    code = ("import sys; import repro.core; from repro.core import "
            "telemetry\nwith telemetry.span('x'): pass\n"
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True,
                         env=dict(os.environ, PYTHONPATH=SRC))
    assert out.stdout.strip() == "False"
