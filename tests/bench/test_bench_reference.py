"""Each configuration's plain reference, at smoke width on the CPU: its
weights from the seed are the served model's, bit for bit, and its
float32 forward agrees with the program's own float32 forward.  (The
reference itself imports nothing of the program; this test does.)"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench_smoke import smoke_config

CONFIGS = {p.stem: json.loads(p.read_text())
           for p in (harness.ROOT / "bench" / "configs").glob("*.json")}


def setup(name, seed):
    from repro.configs import get_smoke_config
    from repro.models import model as M

    conf = smoke_config(CONFIGS[name])
    cfg = get_smoke_config(conf["arch"])
    params = M.init_params(cfg, jax.random.PRNGKey(seed % 2**32))
    return conf, cfg, params, harness.reference_model(conf, seed)


def served_leaves(params) -> dict:
    """The program's parameter tree as ``{"/"-joined path: leaf}``."""
    def name(k):
        return str(k.key if hasattr(k, "key") else k.idx)

    return {"/".join(name(k) for k in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]}


def assert_served_weights(ref, params):
    """Every served leaf, and nothing else, is the reference's, bit for
    bit once both are held in float32."""
    got = ref.served_weights()
    want = served_leaves(params)
    assert set(got) == set(want), (sorted(set(want) - set(got)),
                                   sorted(set(got) - set(want)))
    for k, w in want.items():
        assert np.array_equal(np.asarray(got[k], np.float32),
                              np.asarray(w, np.float32)), k


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_weights_are_the_served_weights(name):
    conf, cfg, params, ref = setup(name, 2**31 + 41)
    assert_served_weights(ref, params)


@pytest.mark.parametrize("fault", ["dropped", "extra", "altered"])
def test_the_weight_comparison_fails_a_reference_that_differs(
        fault, monkeypatch):
    conf, cfg, params, ref = setup("phi-3-vision-4.2b", 2**31 + 43)
    weights = ref.served_weights()
    if fault == "dropped":
        del weights["stages/0/b0/ln2/scale"]
    elif fault == "extra":
        weights["stages/1/b0/attn/wq"] = weights["stages/0/b0/attn/wq"]
    else:
        weights["lm_head"] = weights["lm_head"].at[0, 0].add(1.0)
    monkeypatch.setattr(ref, "served_weights", lambda: weights)
    with pytest.raises(AssertionError):
        assert_served_weights(ref, params)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_forward_matches_the_program_in_float32(name):
    from repro.models import model as M

    conf, cfg, params, ref = setup(name, 9)
    toks = np.random.default_rng(0).integers(0, 256, (2, 48)).astype(
        np.int32)
    cfg32 = cfg.replace(param_dtype="float32", compute_dtype="float32",
                        remat=False)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        want = M.forward_train(cfg32, p32, {"tokens": jnp.asarray(toks)})[0]
    at = np.tile(np.arange(48, dtype=np.int32), (2, 1))
    got = ref.logits_at(toks, at)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # the int8 control departs from it by far more than that
    low = np.asarray(ref.logits_at(toks, at, mode="int8"))
    assert np.abs(low - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_embedding_is_the_pooled_unit_state(name):
    conf, cfg, params, ref = setup(name, 5)
    toks = np.random.default_rng(1).integers(0, 256, (2, 32)).astype(
        np.int32)
    e = np.asarray(ref.embed(toks, np.array([32, 20])))
    np.testing.assert_allclose(np.linalg.norm(e, axis=-1), 1.0, rtol=1e-5)
    # padding after a row's length changes nothing
    toks2 = toks.copy()
    toks2[1, 20:] = 7
    e2 = np.asarray(ref.embed(toks2, np.array([32, 20])))
    np.testing.assert_allclose(e2, e, atol=1e-6)
