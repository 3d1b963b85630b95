"""JAX's persistent compilation cache, placed for a program run.

Every entry point (``chip_smoke.py``, ``examples/*.py``,
``benchmarks/run.py``) calls ``enable_compile_cache()`` once, before its
first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
on its own and this sets nothing.  Otherwise the cache goes to
``.jax_cache/`` at the checkout root: a fixed path, because the path is
part of what a later run must match to find an entry.  Tests never call
this, so their compiles stay out of any cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root: src/repro/launch/compile_cache.py -> parents[3]
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
