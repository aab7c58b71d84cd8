"""Persistent XLA compilation cache of the entry points.

A cold start on a TPU compiles every bucket rung, scan length and kernel
the run visits, which can take minutes; the persistent cache lets a
later process of the same checkout load those executables instead.
Entry points (``chip_smoke.py``, ``launch/serve.main``,
``benchmarks/run.py``) call ``enable_compile_cache`` before their first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# Fixed, so that every process of this checkout finds the same entries
# (the directory is part of what a cache hit needs); listed in .gitignore.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    this function sets nothing; otherwise the cache lives in
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
