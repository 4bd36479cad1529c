"""The persistent compile cache: JAX_COMPILATION_CACHE_DIR wins, else a
fixed directory in the checkout."""

from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.launch.compile_cache import CHECKOUT_CACHE, enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_env_dir_is_left_to_jax(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = enable_compile_cache()
    root = Path(__file__).resolve().parents[1]
    assert got == str(CHECKOUT_CACHE) == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert enable_compile_cache() == got          # same path every call
