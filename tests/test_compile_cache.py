"""repro.compile_cache: the persistent compile cache sits at one fixed path."""

import os

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_config(monkeypatch):
    """Restore the process's cache directory after the test (no compile
    runs in between, so the cache is never initialised here)."""
    was = jax.config.jax_compilation_cache_dir
    yield monkeypatch
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_left_to_jax(cache_config, tmp_path):
    env = str(tmp_path / "from_env")
    cache_config.setenv("JAX_COMPILATION_CACHE_DIR", env)
    jax.config.update("jax_compilation_cache_dir", env)   # as JAX read it
    assert compile_cache.enable_compile_cache() == env
    assert jax.config.jax_compilation_cache_dir == env


def test_default_dir_is_repo_jax_cache(cache_config):
    cache_config.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same path on every call: no temp name, pid or time in it
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
