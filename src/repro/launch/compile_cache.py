"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` from ``main()``, never at
import. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing is changed. Otherwise the cache goes to one fixed directory in the
checkout, ``<repo>/.jax_cache`` (git-ignored): a fixed path, because the
path is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
