"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``benchmarks.run``) call :func:`use_compile_cache` before their first
compile; importing the library never touches the cache.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself, so no
other directory is set. Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (gitignored). The path is part of the cache key,
so it is never temporary, per-process or time-derived.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The directory compiled programs land in."""
    return os.environ.get(ENV) or os.path.join(CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`compile_cache_dir`."""
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
