"""Where the persistent compilation cache goes: JAX_COMPILATION_CACHE_DIR
wins; otherwise a fixed directory inside the checkout."""

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_wins(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before  # nothing set over it


def test_fallback_is_fixed_and_inside_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.setup_compile_cache()
    assert first == ROOT / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == str(first)
    assert compile_cache.setup_compile_cache() == first  # same path on every call
