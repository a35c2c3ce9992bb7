"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, ``benchmarks.run``, the ``repro.launch``
mains) call :func:`enable_compile_cache` once before they compile
anything; library code never does. A cache directory is part of what a
cached program is keyed on, so the path is fixed: never built from a
temp name, a process id or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache", "REPO_CACHE_DIR"]

# <repo>/.jax_cache — git-ignored
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory: the one
    ``JAX_COMPILATION_CACHE_DIR`` names where it is set, and
    :data:`REPO_CACHE_DIR` otherwise. Every compile is cached, however
    short — a chip call starts cold, and the small ones add up."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
