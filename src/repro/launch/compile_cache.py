"""Persistent compilation cache for the entry points.

Called from each launcher's ``main`` (never at import): a first compile
of a full-size search epoch takes minutes, and a later run of the same
program on the same backend reads it back instead.
"""
from __future__ import annotations

import os

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and nothing is changed; otherwise the cache goes to the
    checkout's ``.jax_cache`` — a fixed path, since the path is part of
    the cache key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
