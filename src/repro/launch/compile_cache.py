"""JAX's persistent compilation cache, at one fixed place per checkout.

A cold run on the chip compiles every program again; the persistent cache
lets a later process (or a later call of the same process) load them.  Its
path is part of what makes a hit, so it never moves: the directory named by
``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself, so nothing is set in code), else ``.jax_cache/`` at the root of the
checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns its
    directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
