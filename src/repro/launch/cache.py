"""Placement of JAX's persistent compilation cache for the entry points.

A cold call on the chip compiles everything, so every entry point
(`chip_smoke.py`, `launch.serve`, `launch.train`) keeps compiled programs in
one persistent cache. The directory is part of the cache's key, so it never
moves: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, otherwise
``<repo>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its one directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
