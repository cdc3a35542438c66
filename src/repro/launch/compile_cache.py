"""JAX's persistent compilation cache, kept at one fixed path.

The cache key includes the directory, so a path that moves between runs
never hits: the cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, or
else at ``.jax_cache/`` in the repository root.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. JAX reads
    ``JAX_COMPILATION_CACHE_DIR`` itself, so when it is set nothing else
    is configured here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
