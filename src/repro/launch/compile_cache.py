"""JAX's persistent compilation cache for the entry points.

Every entry point (``repro.launch.serve``, ``chip_smoke.py``,
``benchmarks/run.py``) calls ``enable_compile_cache`` before its first
compile, so processes that run the same programs share compiled code.
``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
itself and nothing here overrides it.  Otherwise the cache lives at a
fixed directory inside the checkout (git ignores it) — a fixed path,
because the path is part of what a cache hit needs.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
