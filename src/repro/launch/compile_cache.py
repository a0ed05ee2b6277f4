"""Where JAX keeps its persistent compilation cache.

Entry points that run on the chip call :func:`configure_compile_cache`
first thing (never at import).  ``JAX_COMPILATION_CACHE_DIR``, when set,
wins: JAX reads it itself and nothing here overrides it.  Otherwise the
cache goes to ``.jax_cache`` at the root of the checkout (git-ignored).
The path is part of the cache key, so it is fixed rather than temporary.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
