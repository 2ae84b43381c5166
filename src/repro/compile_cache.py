"""JAX's persistent compilation cache, kept in one fixed place.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and JAX
reads it itself.  Otherwise the cache lives in ``.jax_cache/`` at the
root of the checkout: a fixed path, because the path is part of what a
cached entry is found by.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory and return
    that directory.  Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
