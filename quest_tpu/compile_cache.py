"""Where JAX keeps its persistent compilation cache.

A cold compile of a full-size program takes tens of seconds to minutes on
a TPU, so every entry point that compiles at full size turns the cache on
through :func:`enable`. The cache directory can be placed from outside:
``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself, wins and nothing
else is set. Without it the cache lives at ``<repo>/.jax_cache`` — a
fixed path, because the path is part of what JAX matches on.
"""

from __future__ import annotations

import os

import jax

__all__ = ["CACHE_ENV", "default_cache_dir", "enable"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<repo>/.jax_cache``: next to the ``quest_tpu`` package."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(repo, ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    placed = os.environ.get(CACHE_ENV, "").strip()
    if placed:
        return placed
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
