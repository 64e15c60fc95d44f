"""JAX's persistent compilation cache for the launchers and `chip_smoke.py`.

Compiling the fleet's device programs is a large share of a cold run on
the chip, so every entry point keeps its compiled programs on disk. The
cache path is part of the cache key, so it is fixed: either what
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself) or
``.jax_cache/`` at the root of the checkout. Library code and tests never
call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: <checkout>/.jax_cache — never derived from a temp dir, a pid or the time
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
