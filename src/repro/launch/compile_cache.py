"""Where JAX keeps its persistent compilation cache.

One helper for every entry point (``chip_smoke.py``, ``launch/serve.py``,
``benchmarks/run.py``).  ``JAX_COMPILATION_CACHE_DIR``, where set, is
JAX's own setting and wins: nothing is set here.  Otherwise the cache
lives at ``<repo>/.jax_cache``.  The path is part of the cache key, so it
is fixed: never built from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
