"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``repro.launch.serve``/``train``,
``benchmarks.run``) call :func:`enable_compile_cache` once before they
compile anything; importing ``repro`` never touches the cache.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
no other directory.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache``: a cache key includes nothing that moves
between runs, so a fixed path is what lets a second run find the first
run's programs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: The checkout root (``src/repro/launch/`` is three levels below it).
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
