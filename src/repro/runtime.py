"""Platform-derived runtime settings: Pallas interpret mode and the
persistent compilation cache.

Neither is a user option. Interpret mode follows the backend JAX runs on,
and the cache directory follows ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it. Nothing here runs at import time.
"""
from __future__ import annotations

import os

import jax

# <repo>/src/repro/runtime.py -> <repo>
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def pallas_interpret(interpret: bool | None = None) -> bool:
    """Interpret mode for a ``pallas_call``: the caller's explicit choice,
    else True exactly when JAX's default backend is the CPU.

    On a TPU the kernels are compiled by Mosaic and never interpreted; a
    kernel the compiler refuses fails with the compiler's own error."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here. Otherwise the cache lives at the fixed
    ``<repo>/.jax_cache``: the path is part of the cache key, so a moving
    (temporary, pid- or time-based) directory would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
