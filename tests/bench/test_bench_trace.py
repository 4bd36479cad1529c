"""The trace reduction against a small recorded trace."""

import os

import pytest

from bench import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")


@pytest.fixture
def tr():
    return T.load_json(DATA)


def test_window_is_the_window_span(tr):
    assert T.window_of(tr) == (50, 650)


def test_busy_is_the_union_of_ops_inside_the_window(tr):
    red = T.reduce(tr)
    # [100,170] (two overlapping ops once) + [300,400] + [405,415]
    # + [500,550], and the op at [20,60) counts only from 50
    assert red["busy_s"] == pytest.approx((70 + 100 + 10 + 50 + 10) * 1e-9)
    assert red["window_s"] == pytest.approx(600e-9)
    assert red["chips"] == 1


def test_idle_gaps_are_labelled_by_the_innermost_span(tr):
    idle = T.reduce(tr)["idle"]
    # [60,100] and [170,300]: inside provider.complete (70..270) at
    # their midpoints; [400,405]: between the plans; [415,500] and
    # [550,650]: inside the second plan
    assert idle["provider.complete"]["gaps"] == 2
    assert idle["provider.complete"]["seconds"] == pytest.approx(170e-9)
    assert idle["window"]["gaps"] == 1
    assert idle["window"]["seconds"] == pytest.approx(5e-9)
    assert idle["plan"]["gaps"] == 2
    assert idle["plan"]["longest_s"] == pytest.approx(100e-9)


def test_ops_by_name_sum_their_time_in_the_window(tr):
    ops = T.reduce(tr)["ops"]
    assert ops["fusion.1"] == pytest.approx(110e-9)
    assert ops["fusion.2"] == pytest.approx(30e-9)
    assert ops["fusion.3"] == pytest.approx(10e-9)
    assert [e[0] for e in T.events_named(tr, "topk_sim")] == [
        "%topk_sim.1 custom-call"]


def test_breakdown_lists_ops_and_gaps_by_time(tr):
    b = T.breakdown(T.reduce(tr))
    assert [n for n, _ in b["device_ops"]][:2] == ["fusion.1",
                                                   "%topk_sim.1 custom-call"]
    assert b["idle_gaps"][0][0].startswith("plan: 2 gaps")
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_trace_without_a_window_reduces_to_none(tr):
    tr["host"] = [h for h in tr["host"] if h[0] != "window"]
    assert T.reduce(tr) is None


def test_op_names_drop_shapes_and_operands():
    hlo = ("%topk_sim.1 = f32[4096,128]{1,0:T(8,128)S(1)} custom-call("
           "f32[262144,2048]{1,0:T(8,128)} %broadcast_divide_fusion), "
           "custom_call_target=\"tpu_custom_call\"")
    assert T.op_name(hlo) == "%topk_sim.1 custom-call"
    loop = ("%while.15 = (s32[]{:T(128)}, bf16[4,32,2048]{2,1,0:T(8,128)}) "
            "while((s32[]{:T(128)}) %tuple.4), condition=%c, body=%b")
    assert T.op_name(loop) == "%while.15 while"
    assert T.op_name("plain") == "plain"


def test_merge_and_gaps():
    assert T.merge([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    assert T.gaps([[1, 4], [5, 7]], 0, 10) == [(0, 1), (4, 5), (7, 10)]


def test_flatten_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("plan"):
            f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.flatten(str(tmp_path))
    names = [h[0] for h in tr["host"]]
    assert "window" in names and "plan" in names
    assert tr["devices"] == {}          # the CPU has no device plane
    red = T.reduce(tr)
    assert red["busy_s"] == 0.0 and red["window_s"] > 0
