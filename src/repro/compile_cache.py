"""Where the entry points keep JAX's persistent compilation cache.

A program's entry point calls ``use_compile_cache()`` once, at the start of
``main`` (never at import, so a library user or a test keeps JAX's own
settings). Compiling the serving pipeline for a chip takes minutes at real
sizes; a cache that a later run finds again turns that into seconds. The
cache key includes the directory, so the directory is fixed: never made
from a temporary directory, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (src/repro/compile_cache.py -> parents[2] is the
# checkout root); listed in .gitignore
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that
    directory and nothing is set here. Otherwise the cache goes to
    ``DEFAULT_DIR`` inside the checkout.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
