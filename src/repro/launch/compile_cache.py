"""JAX's persistent compilation cache, kept at a fixed place.

A compiled step is keyed partly by the cache directory's path, so the
directory must not move between runs: ``JAX_COMPILATION_CACHE_DIR`` when
the environment sets it (JAX reads it itself and this module sets no
other), else ``.jax_cache/`` at the root of the checkout, which git
ignores.  ``JAX_ENABLE_COMPILATION_CACHE=false`` (the test suite sets it)
leaves the cache off.
"""
from __future__ import annotations

import os
import pathlib
from typing import Optional

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on; return its directory
    (None when the cache is disabled)."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
