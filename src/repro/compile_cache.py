"""JAX's persistent compilation cache, kept at one fixed place.

Compiling a sweep program for the TPU takes seconds to minutes, and every
fresh process starts with an empty in-memory cache.  The persistent cache
lets a later process (a second `chip_smoke.py` run, the next benchmark
suite) load what an earlier one compiled.  Its directory must not move
between processes, or nothing is ever found again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# src/repro/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Call before the first compile.  When ``JAX_COMPILATION_CACHE_DIR`` is
    set, JAX reads it itself and no other directory is set here; otherwise
    the cache lives at ``.jax_cache/`` in the checkout.
    """
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
