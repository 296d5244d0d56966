"""JAX persistent compilation cache placement for the entry points.

Call :func:`enable_compile_cache` before the first compile. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
changed; otherwise the cache goes to ``<checkout>/.jax_cache`` (gitignored).
The path is fixed because it is part of the cache key: a directory named
after a pid, a time or a tempdir would never be hit again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
