"""Persistent XLA compile cache for the command-line entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`enable` before their first compile.
Importing ``repro`` never does, so tests and compile rehearsals stay
cache-free.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Fixed cache location used when the environment names none.  The path
#: is part of the cache key, so it must not vary between runs.
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set; otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
