"""JAX's persistent compilation cache, at a path that stays put.

The cache key includes the cache directory, so a directory that moves
between runs (a temporary, or one named from a pid or a time) never hits.
Entry points call :func:`use_compile_cache` once, before their first
compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<repo>/.jax_cache`` (git-ignored)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
