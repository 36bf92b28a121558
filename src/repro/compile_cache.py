"""Persistent XLA compilation cache for the repo's entry-point scripts.

One call, :func:`enable_compile_cache`, made by a script before its first
compile — never on import of ``repro``.  ``$JAX_COMPILATION_CACHE_DIR``
places the cache from outside (JAX reads it itself); otherwise it lives at
one fixed directory inside the checkout, so a later run of the same code
finds what an earlier one compiled.  Every compile is kept, down to the
second or two a Pallas kernel takes.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(ENV_DIR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
