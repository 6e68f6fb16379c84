"""JAX's persistent compilation cache, placed from outside or in the checkout.

Launchers, benchmarks and ``chip_smoke.py`` call :func:`enable_compile_cache`
before their first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing is set here.  Otherwise the cache lives in the
checkout's git-ignored ``.jax_cache`` — a fixed path, because the path is part
of what a later run must match to find an entry.  Tests never call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
