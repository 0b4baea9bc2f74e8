"""JAX's persistent compilation cache, kept at one fixed place.

A cache entry's key includes the directory it lives in, so a cache that
moves from run to run never hits. ``JAX_COMPILATION_CACHE_DIR``, when
set, places the cache from outside (JAX reads it at start-up) and is left
alone; otherwise the cache lives in ``.jax_cache/`` at the repository
root, derived from this file's location. Call ``enable()`` from a
program's ``main``, never at import: a test or a library importing the
package must not start writing a cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
