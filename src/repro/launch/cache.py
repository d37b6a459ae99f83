"""JAX's persistent compilation cache, placed where every call finds it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
nothing.  Otherwise the cache goes to ``<repo>/.jax_cache``: a fixed path,
because the path is part of the cache key and a directory that moves never
hits.  Entry points call :func:`enable_compile_cache`; tests do not.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
