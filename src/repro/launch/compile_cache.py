"""Where JAX keeps its persistent compilation cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache goes to ``.jax_cache/`` at the root
of the checkout. The path is fixed on purpose: it is never built from a
temporary name, a process id or the time, so a later run finds what an
earlier one compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Return the cache directory: ``$JAX_COMPILATION_CACHE_DIR`` if set,
    else ``<checkout>/.jax_cache``, which JAX is then pointed at. Call
    before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
