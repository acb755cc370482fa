"""Where JAX keeps its persistent compilation cache for this checkout.

The path is part of the cache key, so it is fixed: ``.jax_cache/`` at the
checkout root, unless ``JAX_COMPILATION_CACHE_DIR`` is set, in which case
JAX reads that variable itself and nothing here overrides it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache():
    """Point JAX's persistent compilation cache at ``CACHE_DIR`` unless
    ``JAX_COMPILATION_CACHE_DIR`` already names one. Call before the first
    compile; the launchers call it from ``main``."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
