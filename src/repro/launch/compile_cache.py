"""JAX's persistent compilation cache for the entry points.

Called by the drivers (``serve.main``, ``train.main``) and by
``chip_smoke.py`` — never on import, never from tests.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache lives at the fixed ``<repo>/.jax_cache``:
the directory is part of what a later run must find again, so it never
depends on a temporary directory, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on; returns the directory in use.  CPU
    compiles are not persisted: they take seconds, and loading one back
    makes the CPU backend warn about host features on every hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
