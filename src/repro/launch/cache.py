"""Persistent XLA compilation cache for the command-line entry points.

Call ``enable_compile_cache()`` from an entry point's ``main``, never at
import: tests and library users keep JAX's own default.
"""
from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is changed. Otherwise the cache goes to ``<repo>/.jax_cache``:
    a fixed path, because the path is part of the cache key and a
    directory that moves never hits.
    """
    path = os.environ.get(_ENV)
    if path:
        return path
    import jax
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
