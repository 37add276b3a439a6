"""Where JAX keeps its persistent compilation cache.

A cache that moves between runs never hits (the directory is part of the
lookup), so the place is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is
set (JAX reads it itself and nothing here overrides it), otherwise
``<checkout>/.jax_cache``, which ``.gitignore`` lists.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache(default_dir: Path = CHECKOUT / ".jax_cache") -> str:
    """Turn the persistent compilation cache on before the first compile;
    returns the directory in use."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(default_dir)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
