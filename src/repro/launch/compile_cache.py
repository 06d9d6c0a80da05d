"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the directory, so a cache only hits when every run
uses the same path. ``JAX_COMPILATION_CACHE_DIR``, when set, is that path
(JAX reads the variable itself and this module sets nothing). Otherwise
the cache lives in ``.jax_cache/`` at the root of the checkout, which git
ignores.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
