"""JAX's persistent compilation cache, kept at one fixed place.

Every process an entry point starts compiles its programs again unless a
persistent cache holds them.  Entry points (``chip_smoke.py``, the
launcher, ``benchmarks/run_all.py``) call :func:`enable` once, before
their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# inside the checkout and never derived from a pid, a temp name or a clock:
# a cache that moves between runs is never hit
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache is ``CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
