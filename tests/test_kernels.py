"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rg_lru.ops import rg_lru
from repro.kernels.rg_lru.ref import rg_lru_ref
from repro.kernels.ssm_scan.ops import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref
from repro.kernels.topk_sim.ops import topk_sim
from repro.kernels.topk_sim.ref import topk_sim_ref

TOLS = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dtype):
    return TOLS[jnp.bfloat16] if dtype == jnp.bfloat16 else TOLS[jnp.float32]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,KH,hd,causal,window,bq,bk",
    [
        (2, 64, 4, 2, 32, True, 0, 16, 16),
        (1, 96, 8, 8, 16, True, 0, 32, 16),
        (2, 48, 4, 1, 16, True, 16, 16, 16),     # MQA + sliding window
        (1, 80, 6, 2, 64, False, 0, 16, 32),     # bidirectional (encoder)
        (1, 33, 4, 2, 16, True, 0, 16, 16),      # ragged -> padding path
    ])
def test_flash_attention(rng, B, S, H, KH, hd, causal, window, bq, bk,
                         dtype):
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, KH, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, KH, hd)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KH,hd,window,bs",
                         [(3, 100, 8, 4, 32, 0, 32),
                          (2, 64, 4, 4, 16, 16, 16),
                          (1, 257, 8, 2, 64, 0, 64)])
def test_decode_attention(rng, B, S, H, KH, hd, window, bs, dtype):
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), dtype)
    kc = jnp.asarray(rng.standard_normal((B, S, KH, hd)), dtype)
    vc = jnp.asarray(rng.standard_normal((B, S, KH, hd)), dtype)
    pos = jnp.asarray(rng.integers(0, S, B), jnp.int32)
    out = decode_attention(q, kc, vc, pos, window=window, block_s=bs)
    ref = decode_attention_ref(q, kc, vc, pos, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,di,N,chunk,bd",
                         [(2, 80, 48, 8, 16, 16),
                          (1, 128, 64, 16, 32, 64),
                          (2, 33, 24, 4, 16, 8)])
def test_ssm_scan(rng, B, S, di, N, chunk, bd, dtype):
    x = jnp.asarray(rng.standard_normal((B, S, di)), dtype)
    dt = jnp.asarray(np.abs(rng.standard_normal((B, S, di))) * 0.1, dtype)
    Bm = jnp.asarray(rng.standard_normal((B, S, N)), dtype)
    Cm = jnp.asarray(rng.standard_normal((B, S, N)), dtype)
    Al = jnp.asarray(np.log(np.abs(rng.standard_normal((di, N))) + 0.5),
                     jnp.float32)
    D = jnp.asarray(rng.standard_normal((di,)), jnp.float32)
    out = ssm_scan(x, dt, Bm, Cm, Al, D, chunk=chunk, block_d=bd)
    ref = ssm_scan_ref(x, dt, Bm, Cm, Al, D)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5 * _tol(dtype), rtol=5 * _tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,di,chunk,bd",
                         [(2, 80, 48, 16, 16), (1, 200, 32, 64, 32)])
def test_rg_lru(rng, B, S, di, chunk, bd, dtype):
    a = jnp.asarray(rng.uniform(0.5, 0.999, (B, S, di)), dtype)
    b = jnp.asarray(rng.standard_normal((B, S, di)), dtype)
    out = rg_lru(a, b, chunk=chunk, block_d=bd)
    ref = rg_lru_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5 * _tol(dtype), rtol=5 * _tol(dtype))


@pytest.mark.parametrize("N,D,Q,k,bn", [(1000, 32, 5, 10, 64),
                                        (513, 16, 3, 7, 128),
                                        (64, 8, 1, 64, 16),
                                        (5, 8, 2, 9, 64),       # k > N
                                        (1, 4, 2, 3, 64)])      # 1-doc
def test_topk_sim(rng, N, D, Q, k, bn):
    c = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((Q, D)), jnp.float32)
    s, i = topk_sim(c, q, k, block_n=bn)
    s_ref, i_ref = topk_sim_ref(c, q, min(k, N))
    assert s.shape == (Q, min(k, N))            # k capped at N
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=1e-5,
                               rtol=1e-5)
    assert (np.asarray(i) == np.asarray(i_ref)).all()


def test_topk_sim_empty_corpus_and_queries(rng):
    c = jnp.zeros((0, 8), jnp.float32)
    q = jnp.asarray(rng.standard_normal((3, 8)), jnp.float32)
    s, i = topk_sim(c, q, 5)
    assert s.shape == (3, 0) and i.shape == (3, 0)
    s, i = topk_sim(jnp.asarray(rng.standard_normal((4, 8)), jnp.float32),
                    jnp.zeros((0, 8), jnp.float32), 2)
    assert s.shape == (0, 2) and i.shape == (0, 2)


def test_topk_sim_interpret_default_is_backend_aware():
    from repro.kernels import resolve_interpret
    # explicit settings win; None resolves per backend (the CI host is
    # CPU-only, where no compiled Pallas lowering exists)
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    expected = jax.default_backend() == "cpu"
    assert resolve_interpret(None) is expected


@pytest.mark.slow
def test_model_with_pallas_matches_reference(rng):
    """The use_pallas=True model path equals the pure-jnp path end to end."""
    from repro.configs import get_smoke_config
    from repro.configs.specs import make_batch
    from repro.models import model as M
    from repro.models.config import ShapeCell

    for arch in ["olmo-1b", "falcon-mamba-7b", "recurrentgemma-9b"]:
        cfg = get_smoke_config(arch).replace(remat=False)
        batch = make_batch(cfg, ShapeCell("s", 32, 2, "train"))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        ref_logits, _ = M.forward_train(cfg, params, batch)
        pl_logits, _ = M.forward_train(cfg.replace(use_pallas=True), params,
                                       batch)
        # smoke configs run in bf16 — kernel/ref differ by rounding only;
        # accumulated bf16 rounding across layers reaches a few ulp on
        # logits of magnitude ~2, so 6e-2 abs (seed atol=3e-2 flaked at
        # 0.0401 on 5/16384 elements)
        np.testing.assert_allclose(np.asarray(pl_logits, np.float32),
                                   np.asarray(ref_logits, np.float32),
                                   atol=6e-2, rtol=6e-2)
