"""The traffic generator: the same work for every seed, with other
words, and rows no cache can serve."""

import json

import pytest

from bench import harness
from bench import workload as W

TRAFFIC = {p.stem: json.loads(p.read_text())
           for p in (harness.ROOT / "bench" / "traffic").glob("*.json")}


@pytest.mark.parametrize("name", sorted(TRAFFIC))
def test_every_seed_sends_the_same_lengths_with_other_words(name):
    t = TRAFFIC[name]
    for index in range(3):
        a = W.plan_rows(t, 1, index)
        b = W.plan_rows(t, 2**31 + 11, index)
        assert list(map(len, a)) == list(map(len, b))
        assert a != b
        lo, hi = t["row_bytes"]["min"], t["row_bytes"]["max"]
        assert all(lo <= len(r) <= hi for r in a)
        assert len(a) == t["rows_per_plan"]


@pytest.mark.parametrize("name", sorted(TRAFFIC))
def test_rows_are_unique_across_plans_and_reproducible(name):
    t = TRAFFIC[name]
    rows = [r for i in range(20) for r in W.plan_rows(t, 7, i)]
    assert len(set(rows)) == len(rows)
    assert W.plan_rows(t, 7, 3) == W.plan_rows(t, 7, 3)
    warm = W.warm_rows(t, "max", 7) + W.warm_rows(t, "min", 7)
    assert not set(warm) & set(rows)
    assert {len(r) for r in W.warm_rows(t, "max", 7)} == {
        t["row_bytes"]["max"]}


def test_corpus_texts_are_unique_and_in_range():
    t = json.loads(json.dumps(TRAFFIC["rag_scan"]))
    t["corpus"]["rows"] = 5000
    texts = W.corpus_texts(t, 3)
    assert len(set(texts)) == 5000
    spec = t["corpus"]["row_bytes"]
    assert all(spec["min"] <= len(x) <= spec["max"] for x in texts)
    assert texts == W.corpus_texts(t, 3) != W.corpus_texts(t, 4)


def test_corpus_vectors_follow_the_seed():
    import numpy as np

    a = np.asarray(W.corpus_vectors(2**31 + 1, 64, 16))
    assert a.shape == (64, 16) and a.dtype == np.float32
    assert np.array_equal(a, np.asarray(W.corpus_vectors(2**31 + 1, 64, 16)))
    assert not np.array_equal(a, np.asarray(W.corpus_vectors(2, 64, 16)))


def test_unknown_operator_is_refused():
    t = dict(TRAFFIC["batch_map_32"], plan=[{"op": "llm_unknown"}])
    with pytest.raises(ValueError, match="no operator"):
        W.build_plan(None, t, ["r0 x"], {"gen": {}}, None)
