"""Persistent compile cache for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``)
call :func:`enable_compile_cache` once at start-up; importing ``repro``
never touches the cache.  The cache key includes its directory, so the
directory must not move between runs: it is ``$JAX_COMPILATION_CACHE_DIR``
when that is set (JAX reads the variable itself) and otherwise a fixed
``.jax_cache/`` at the checkout root.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
