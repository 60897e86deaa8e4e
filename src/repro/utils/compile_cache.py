"""JAX's persistent compilation cache, placed from outside the code.

Entry points call :func:`use_compile_cache` before their first jitted
call.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing here overrides it.  Otherwise the cache goes to a fixed
``<repo>/.jax_cache``: the path is part of the cache key, so it holds
no temp name, pid or time, and a later run from the same checkout hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Returns the cache directory in effect."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
