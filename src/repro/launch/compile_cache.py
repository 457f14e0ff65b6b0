"""Where JAX keeps its persistent compilation cache.

Each entry point calls :func:`use_compile_cache` at the start of
``main()``. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this sets nothing. Otherwise the cache goes to a fixed
``.jax_cache/`` at the checkout root: the directory is part of the
cache's key, so a path built from a temp name, a pid or the time would
never be hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (``src/repro/launch/`` is three levels below it)
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory and
    return that directory."""
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
