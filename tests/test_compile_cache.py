"""Where the entry points keep JAX's persistent compilation cache."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config(monkeypatch):
    """Restore JAX's cache directory after the test (nothing compiles in
    between, so the cache itself is never opened)."""
    was = jax.config.jax_compilation_cache_dir
    yield monkeypatch
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_stands(cache_config, tmp_path):
    cache_config.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_dir_at_checkout_root(cache_config):
    cache_config.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same directory on every call: a second run finds the first's
    assert compile_cache.enable_compile_cache() == want


def test_off_outside_a_checkout(cache_config, tmp_path):
    cache_config.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache_config.setattr(compile_cache, "_ROOT", tmp_path)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
