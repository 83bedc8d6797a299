"""JAX's persistent compilation cache for the entry points.

The cache key includes the directory, so it must stay at one path from run
to run: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it; otherwise the cache lives in ``.jax_cache/`` at
the repository root. Entry points call :func:`enable_compile_cache` from
``main()``; importing a module never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
