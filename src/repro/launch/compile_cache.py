"""JAX's persistent compilation cache, kept at one fixed place.

A process that compiles a program JAX has already compiled in an earlier
process reads it back from this cache instead of compiling again. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that variable itself and
nothing here changes it. Otherwise the cache lives in ``.jax_cache/`` at
the root of the checkout (git-ignored): always the same directory, never
a temporary, per-process or per-run name, so a later run finds it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at the fixed directory (unless the
    environment already names one); returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
