"""JAX's persistent compilation cache, kept at one fixed place.

A compiled program is found again only under the same cache directory, so
the directory never depends on a temp name, a pid or a time.  Entry points
call ``enable_compile_cache()`` from ``main()``; importing a module never
touches JAX's configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# <checkout>/.jax_cache (git-ignored); this file is <checkout>/src/repro/launch/
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins:
    nothing else is set.  Otherwise the cache sits in the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
