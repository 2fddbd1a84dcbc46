"""JAX persistent compilation cache location for the entry points.

`enable_compile_cache()` is called from each entry point's `main()` (and
from `chip_smoke.py`), never at import. If `JAX_COMPILATION_CACHE_DIR` is
set, JAX already reads it and nothing else is set. Otherwise the cache
goes to `.jax_cache/` at the root of the checkout: a fixed path, because
the path is part of what the cache is keyed on, so a directory that moves
between runs never hits.
"""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at `$JAX_COMPILATION_CACHE_DIR`
    when it is set, else at DEFAULT_CACHE_DIR. Returns the directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
