"""Persistent XLA compilation cache for the entry points.

Compiling the served programs at a real graph size takes seconds to
minutes, so the entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) keep compiled programs on disk across processes.
Call :func:`enable_compile_cache` at startup, never at import.
"""
from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache``: this file is ``<checkout>/src/repro/launch/``.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and
    nothing is set here.  Otherwise the cache goes to the fixed
    :data:`DEFAULT_DIR`, which the next process finds again; a temporary
    or per-process path would start empty every time."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
