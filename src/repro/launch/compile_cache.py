"""Persistent XLA compilation cache, placed from outside.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it on
its own and this module sets no other directory.  Otherwise the cache
lives at one fixed path inside the checkout, ``<repo>/.jax_cache``
(listed in ``.gitignore``).  The path is part of every cache key, so it
is never derived from a temp name, a pid or the time.

Call ``enable_compile_cache()`` before the first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["compile_cache_dir", "enable_compile_cache"]

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
