"""JAX's persistent compilation cache for the program's entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once before
they compile anything; importing ``repro`` never does, so tests keep
JAX's defaults.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
this sets no other directory. Otherwise the cache lives at a fixed path
inside the checkout (``<repo>/.jax_cache``, git-ignored): the path is
part of the cache key, so a directory that moved would never hit.
"""

from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
