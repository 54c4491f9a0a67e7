"""JAX persistent compilation cache, kept at one fixed place.

The cache directory is part of what a cached executable is found by, so a
directory that moves from run to run never hits.  Call
:func:`enable_compile_cache` once, before the first compile, from a
program's entry point (``chip_smoke.py``, ``benchmarks/run.py``).
"""

from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# <repo>/.jax_cache: this file lives at <repo>/src/repro/compile_cache.py
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and it is left alone.  Otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
