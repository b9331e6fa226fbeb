"""Where JAX keeps its persistent compilation cache.

Call :func:`enable_compile_cache` at the start of a run, never at import
time (lint rule RPR004).  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it and this sets nothing.  Otherwise the cache goes to the fixed
``<repo>/.jax_cache`` (listed in ``.gitignore``): the path is part of the
cache key, so a directory that moved between runs would never hit.
"""
from __future__ import annotations

import os

__all__ = ["enable_compile_cache", "DEFAULT_DIR"]

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
