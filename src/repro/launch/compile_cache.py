"""JAX's persistent compilation cache for the entry-point scripts.

``chip_smoke.py``, ``repro.launch.serve`` and ``benchmarks/run.py`` call
``enable_compile_cache()`` once at start-up; library imports never touch the
cache. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
this module sets nothing. Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout: a fixed path (never built from a temporary name, a pid
or the time), so a second process or run in the same checkout finds what
the first one compiled. ``.gitignore`` lists the directory.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root is three levels up
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at ``DEFAULT_CACHE_DIR`` unless the
    environment names one; returns the directory the cache uses."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    path = str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
