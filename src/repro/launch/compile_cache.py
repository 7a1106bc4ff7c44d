"""Where JAX keeps its persistent compilation cache.

``enable_compile_cache()`` is called by the launchers' ``main`` and by
``chip_smoke.py``, before their first compile: JAX decides whether a
process uses the cache at that compile. Importing this module changes
nothing.

The cache key includes the directory, so the directory never moves: it is
``$JAX_COMPILATION_CACHE_DIR`` where that is set, and otherwise the fixed
``<repo>/.jax_cache`` (gitignored) — never a temporary name, a pid or a
time, which would miss on every run.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory. Only the directory is set: every other cache
    option keeps the value JAX or its environment gave it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
