"""Where the entry points keep JAX's persistent compilation cache.

A full-width unified step takes tens of seconds to compile; the persistent
cache lets a later process (another run, another entry point) load it
instead.  The cache key includes the directory, so the directory is fixed:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads it
itself), otherwise ``.jax_cache`` at the root of this checkout — never a
name built from a temporary directory, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
