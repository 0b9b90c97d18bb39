"""JAX's persistent compilation cache for the entry points.

A full-width serving step takes tens of seconds to compile; the persistent
cache lets the next process with the same program skip that. JAX keys the
cache on its directory, so the directory is fixed: where the environment
sets ``JAX_COMPILATION_CACHE_DIR`` JAX reads it itself and nothing here
overrides it; otherwise the cache lives at ``CACHE_DIR`` inside the
checkout (listed in ``.gitignore``). Entry points call
:func:`enable_compile_cache` before their first compile; importing a module
never changes the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
