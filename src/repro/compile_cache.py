"""JAX's persistent compilation cache, placed for the entry points.

Call ``enable_compile_cache()`` from a ``main`` before anything compiles;
importing this module changes nothing. The cache goes where
``JAX_COMPILATION_CACHE_DIR`` says when it is set, else to ``.jax_cache``
at the root of the checkout: a fixed path, because the path is part of
what a cache hit needs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
