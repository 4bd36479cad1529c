"""Serving engine: continuous batching == one-shot oracle; chunked prefill."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.batching import ContextOverflowError
from repro.models import model as M
from repro.serving.engine import ServingEngine


def _oracle(cfg, params, prompt, n_new, cache_len=64):
    batch = {"tokens": jnp.asarray([prompt], jnp.int32)}
    lg, cache, pos = M.prefill(cfg, params, batch, cache_len)
    toks = [int(jnp.argmax(lg[0, -1]))]
    for i in range(n_new - 1):
        lg, cache = M.decode_step(
            cfg, params, jnp.asarray([[toks[-1]]], jnp.int32), cache,
            jnp.int32(pos + i))
        toks.append(int(jnp.argmax(lg[0, 0])))
    return toks


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["olmo-1b", "falcon-mamba-7b",
                                  "recurrentgemma-9b"])
def test_engine_matches_oracle(arch, rng):
    cfg = get_smoke_config(arch).replace(remat=False, capacity_factor=16.0)
    eng = ServingEngine(cfg, n_slots=2, max_context=64, chunk=8, seed=0)
    prompt = list(rng.integers(0, cfg.vocab_size, 21))
    out = eng.generate(prompt, max_new_tokens=5)
    assert out == _oracle(cfg, eng.params, prompt, 5)


def test_concurrent_requests_isolated(rng):
    """Two in-flight requests produce the same tokens as each alone."""
    cfg = get_smoke_config("olmo-1b").replace(remat=False)
    p1 = list(rng.integers(0, cfg.vocab_size, 21))
    p2 = list(rng.integers(0, cfg.vocab_size, 13))

    eng = ServingEngine(cfg, n_slots=2, max_context=64, chunk=8, seed=0)
    r1, r2 = eng.submit(p1, 5), eng.submit(p2, 5)
    eng.run_until_idle()

    solo = ServingEngine(cfg, n_slots=2, max_context=64, chunk=8, seed=0)
    assert r1.generated == solo.generate(p1, 5)
    solo2 = ServingEngine(cfg, n_slots=2, max_context=64, chunk=8, seed=0)
    assert r2.generated == solo2.generate(p2, 5)


def test_more_requests_than_slots(rng):
    cfg = get_smoke_config("olmo-1b").replace(remat=False)
    eng = ServingEngine(cfg, n_slots=2, max_context=64, chunk=8)
    reqs = [eng.submit(list(rng.integers(0, cfg.vocab_size, 9)), 3)
            for _ in range(5)]
    eng.run_until_idle()
    assert all(r.finished for r in reqs)
    assert all(len(r.generated) == 3 for r in reqs)


def test_oversized_request_rejected():
    """A request that cannot fit raises at submit instead of finishing
    with no tokens; the engine stays idle and serves the next one."""
    cfg = get_smoke_config("olmo-1b").replace(remat=False)
    eng = ServingEngine(cfg, n_slots=1, max_context=32, chunk=8)
    with pytest.raises(ContextOverflowError):
        eng.submit(list(range(30)), max_new_tokens=10)
    with pytest.raises(ContextOverflowError):
        eng.generate(list(range(30)), max_new_tokens=10)
    assert not eng.waiting and not any(eng.active)
    assert len(eng.generate(list(range(22)), max_new_tokens=10)) == 10


def _local_provider(max_context):
    from repro.core.provider import LocalJaxProvider
    return LocalJaxProvider("olmo-1b", max_context=max_context)


def test_provider_overflow_counts_its_own_tokens():
    """The provider checks its byte-level token count, not the planner's
    0.33-per-character estimate: a prompt the estimate lets through but
    whose bytes overflow the engine raises ContextOverflowError, and the
    engine is never reached."""
    from repro.core.metaprompt import build_metaprompt
    from repro.core.provider import estimate_tokens
    from repro.core.resources import ModelResource

    prov = _local_provider(max_context=512)
    model = ModelResource(name="local", version=1, arch="olmo-1b",
                          context_window=4096, max_output_tokens=2)
    mp = build_metaprompt("complete", "echo", [{"t": "x" * 200}], "xml")
    assert estimate_tokens(mp.text) + 2 < 512 < len(mp.text.encode())
    with pytest.raises(ContextOverflowError):
        prov.complete(model, mp, 1)
    assert prov.stats.calls == 0 and prov.engine.steps == 0
    ok = build_metaprompt("complete", "echo", [{"t": "x"}], "xml")
    assert len(prov.complete(model, ok, 1)) == 1
    assert prov.engine.steps > 0


def test_provider_overflow_backs_off_to_null():
    """Through llm_complete the overflow drives the adaptive batcher: the
    batch splits down to single tuples, the row that still cannot fit
    is NULL, and every other row is answered."""
    from repro.core import SemanticContext, llm_complete

    prov = _local_provider(max_context=512)
    ctx = SemanticContext(provider=prov)
    rows = [{"t": f"r{i}"} for i in range(4)] + [{"t": "y" * 300}]
    out = llm_complete(ctx, {"model": "local", "context_window": 4096,
                             "max_output_tokens": 2},
                       {"prompt": "echo"}, rows)
    assert out[-1] is None
    assert all(o is not None for o in out[:-1])
    rep = ctx.last_report()
    assert rep.retries > 0 and rep.nulls == 1


def test_embedding_deterministic_and_normalised():
    cfg = get_smoke_config("olmo-1b").replace(remat=False)
    eng = ServingEngine(cfg, n_slots=1, max_context=64)
    e1 = eng.embed([1, 2, 3, 4])
    e2 = eng.embed([1, 2, 3, 4])
    e3 = eng.embed([5, 6, 7])
    assert np.allclose(e1, e2)
    assert not np.allclose(e1, e3)
    assert abs(np.linalg.norm(e1) - 1.0) < 1e-3


def test_chunked_prefill_equals_full_prefill(rng):
    """prefill_chunk chain == one-shot prefill (cache + logits)."""
    for arch in ["olmo-1b", "falcon-mamba-7b"]:
        cfg = get_smoke_config(arch).replace(remat=False)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        prompt = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
        full = {"tokens": jnp.asarray([prompt])}
        lg_full, cache_full, _ = M.prefill(cfg, params, full, 32)

        cache = M.init_cache(cfg, 1, 32)
        off = 0
        for c0 in range(0, 16, 8):
            chunk = jnp.asarray([prompt[c0:c0 + 8]])
            lg, cache = M.prefill_chunk(cfg, params, chunk, cache,
                                        jnp.int32(off))
            off += 8
        np.testing.assert_allclose(np.asarray(lg[:, -1]),
                                   np.asarray(lg_full[:, -1]),
                                   atol=2e-3, rtol=2e-3)


def test_score_matches_float32_forward(rng):
    """engine.score runs the request split (whole chunks through chunked
    prefill, the tail through decode) and its teacher-forced logits
    track a float32 forward of the same parameters."""
    cfg = get_smoke_config("olmo-1b").replace(remat=False)
    eng = ServingEngine(cfg, n_slots=2, max_context=64, chunk=8, seed=0)
    toks = [int(t) for t in rng.integers(0, cfg.vocab_size, 29)]
    got = eng.score(toks)
    assert got.shape == (29, cfg.vocab_size)
    assert eng.steps == 0 and not any(eng.active)

    cfg32 = cfg.replace(param_dtype="float32", compute_dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), eng.params)
    with jax.default_matmul_precision("highest"):
        ref, _ = M.forward_train(cfg32, p32,
                                 {"tokens": jnp.asarray([toks], jnp.int32)})
    ref = np.asarray(ref[0, :, :cfg.vocab_size])
    # bf16 serving vs f32 reference: a few bf16 ulps of |logits| <= ~4
    np.testing.assert_allclose(got, ref, atol=0.06, rtol=0)
    assert (got.argmax(-1) == ref.argmax(-1)).mean() > 0.9
    with pytest.raises(ContextOverflowError):
        eng.score(list(range(65)))
