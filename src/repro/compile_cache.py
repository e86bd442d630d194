"""Persistent XLA compile cache for the entry points.

The entry points (``chip_smoke.py``, ``repro.launch.serve.main``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once at start-up;
nothing calls it when a module is imported, and tests never call it.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there
and this sets nothing.  Otherwise the cache goes to a fixed directory inside
the checkout (``<repo>/.jax_cache``, git-ignored): the directory is part of
the cache key, so it is never derived from a temp name, a PID or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
