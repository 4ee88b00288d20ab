"""Persistent XLA compilation cache for the entry points.

JAX keys a cache entry partly on the cache directory, so the directory must
not move between runs: it is either the one ``JAX_COMPILATION_CACHE_DIR``
names (JAX reads that variable itself, and nothing is set here) or the
fixed ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compilation of the process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
