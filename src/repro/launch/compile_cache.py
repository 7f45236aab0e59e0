"""Where JAX keeps its persistent compilation cache.

Entry points (``repro.launch.serve``, ``chip_smoke.py``,
``benchmarks/run.py``) call ``use_compile_cache()`` at startup, never at
import.  ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache and
nothing here overrides it; otherwise the cache lives at ``CACHE_DIR``,
one fixed git-ignored directory of the checkout.  The path is part of
each entry's key, so it must not move between runs.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache.  -> its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
