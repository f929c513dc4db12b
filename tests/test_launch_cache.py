"""The entry points' compile-cache rule (repro.launch.cache)."""
import os

import jax

from repro.launch import cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_cache_dir_is_left_to_jax(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_a_fixed_repo_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert cache.enable_compile_cache() == path      # no temp/pid/time
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
