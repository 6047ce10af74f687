"""Persistent compilation cache placement for the entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set this module
leaves it alone.  Otherwise the cache goes to one fixed directory inside the
checkout (git-ignored): the directory is part of each entry's key, so a path
built from a temporary name, a pid or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Point JAX's persistent compile cache at ``REPO_CACHE_DIR`` unless
    ``JAX_COMPILATION_CACHE_DIR`` already places it.  Call from ``main``."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
