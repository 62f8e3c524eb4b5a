"""Where JAX's persistent compilation cache lives.

A compiled program is found again by a later process only when both
look in the same directory, so the repository keeps exactly one:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
that variable itself, and nothing here overrides it), else the fixed
``<repo>/.jax_cache``.  Entry points
that compile call :func:`enable_compile_cache` before their first
compile; nothing else in the code sets a cache directory.
"""
from __future__ import annotations

import os

import jax

#: the checkout root (src/repro/launch/cache.py -> three levels up)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
