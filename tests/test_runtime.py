"""launch/runtime.py: where the persistent compilation cache goes."""

from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import runtime

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restore JAX's cache settings after a test changes them."""
    was = (jax.config.jax_compilation_cache_dir, jax.config.jax_enable_compilation_cache)
    yield
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_enable_compilation_cache", was[1])
    compilation_cache.reset_cache()


def test_cache_dir_from_env_var(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path))
    assert runtime.enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert jax.config.jax_enable_compilation_cache


def test_cpu_run_without_env_var_caches_nothing(monkeypatch, cache_config):
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compilation_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_fixed_and_git_ignored():
    assert runtime.DEFAULT_CACHE_DIR == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
