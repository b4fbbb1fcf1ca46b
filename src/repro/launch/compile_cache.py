"""JAX's persistent compilation cache for the entry points.

Entry points call :func:`enable_compile_cache` before their first compile;
nothing configures the cache at import time.  ``$JAX_COMPILATION_CACHE_DIR``
wins when it is set (JAX reads it itself).  Otherwise the cache lives at the
fixed ``<repo>/.jax_cache``: the directory is part of the cache key, so a
name that moved between runs (temp dir, pid, time) would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent cache at its directory and return that path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
