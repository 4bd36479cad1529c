"""Compile the served path's kernels and olmo-1b's full-width decode step
for a described TPU v5e chip, with no chip attached.

Nothing runs: the TPU compiler either accepts each program at its real
shape or raises what the chip's compiler would raise (tiling, VMEM, HBM
fit).  Interpret-mode tests cannot see those refusals.  The topology is
described inside a fixture, never while a module is imported, so every
test worker collects the same tests and only the worker that is given
this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.topk_sim.ops import topk_sim
from repro.models import model as M

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_topk_sim_compiles_at_one_chip_corpus(one_chip):
    # 262,144 x 2048 float32 (2 GiB): the corpus one chip holds for scans
    c = _spec((262_144, 2048), jnp.float32, one_chip)
    q = _spec((8, 2048), jnp.float32, one_chip)
    compiled, hlo = _compile(
        lambda c, q: topk_sim(c, q, 10, interpret=False), c, q)
    assert "tpu_custom_call" in hlo
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_decode_attention_compiles_at_olmo_width(one_chip):
    # olmo-1b decode: 4 slots, 2048-token cache, 16 heads of 128, bf16
    q = _spec((4, 1, 16, 128), jnp.bfloat16, one_chip)
    kv = _spec((4, 2048, 16, 128), jnp.bfloat16, one_chip)
    pos = _spec((4,), jnp.int32, one_chip)
    _, hlo = _compile(
        lambda q, k, v, p: decode_attention(q, k, v, p, interpret=False),
        q, kv, kv, pos)
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles_at_olmo_width(one_chip):
    x = _spec((1, 2048, 16, 128), jnp.bfloat16, one_chip)
    _, hlo = _compile(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        x, x, x)
    assert "tpu_custom_call" in hlo


def test_olmo_1b_decode_step_compiles_at_full_width(one_chip):
    cfg = get_config("olmo-1b").replace(remat=False)
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.d_ff,
            cfg.vocab_size) == (16, 2048, 16, 8192, 50_304)

    def place(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                            tree)

    params = place(jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    cache = place(jax.eval_shape(lambda: M.init_cache(cfg, 4, 2048)))
    toks = _spec((4, 1), jnp.int32, one_chip)
    pos = _spec((4,), jnp.int32, one_chip)
    compiled, _ = _compile(
        lambda p, t, c, pos: M.decode_step(cfg, p, t, c, pos),
        params, toks, cache, pos)
    assert _device_bytes(compiled) < V5E_HBM_BYTES
