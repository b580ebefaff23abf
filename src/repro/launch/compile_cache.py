"""Where the entry points keep JAX's persistent compilation cache.

A compiled program is found again only under the same cache directory,
so the directory must not move between runs: no temporary, process- or
time-based path.
"""
from __future__ import annotations

import os
import pathlib

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Use ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
    itself); otherwise keep the cache in ``<checkout>/.jax_cache``.
    Returns the directory in use. Call from an entry point only."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
