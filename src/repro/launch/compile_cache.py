"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/campaign.py``,
``repro.launch.codesign_serve``) call :func:`enable_compile_cache` before
their first compile; nothing calls it at import.  A cache key includes
the cache path, so the path is fixed: ``JAX_COMPILATION_CACHE_DIR`` when
it is set (JAX reads it itself and nothing is overridden), otherwise
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root is three levels up
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
