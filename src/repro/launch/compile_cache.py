"""JAX's persistent compilation cache, kept at one fixed place.

The entry points (``python -m repro``, ``python -m repro.launch.train`` and
``chip_smoke.py``) call :func:`enable_compile_cache` once at start-up, so a
second run of the same program loads its executables instead of compiling
them again. Library code and the tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the environment variable JAX itself reads for the cache directory
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the fallback directory: ``<repo>/.jax_cache`` (this file is
#: ``<repo>/src/repro/launch/compile_cache.py``), ignored by git
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
    directory and nothing else is set here. Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`, a fixed path: the path is part of what a
    later run has to find again, so it is never a temporary, per-process
    or per-run directory."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
