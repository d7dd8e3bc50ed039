"""Persistent XLA compilation cache for the entry points.

``enable_compile_cache()`` is called at the top of each entry point's
``main`` (serve, train, ``chip_smoke.py``, the benchmark drivers) and
never at import.  The cache directory is part of the cache key, so it is
one fixed path: ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads
it itself, and nothing else is configured here), otherwise ``.jax_cache``
at the root of the checkout (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
