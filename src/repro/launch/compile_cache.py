"""JAX's persistent compilation cache for the repo's entry points.

Call :func:`enable` once, at the top of a command-line entry point, before
anything compiles.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
keeps its cache there and nothing is changed.  Otherwise the cache goes to
``<repo>/.jax_cache`` (listed in ``.gitignore``): a fixed path, because the
path is part of what a cached entry is found by.  Library code and tests
never call this.
"""
from __future__ import annotations

import os
import pathlib

#: the fixed fallback cache directory, at the root of the checkout
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str | None:
    """Turn the persistent compilation cache on; returns its directory
    (None where jax is not installed: a numpy-only install compiles
    nothing)."""
    try:
        import jax
    except ImportError:
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
