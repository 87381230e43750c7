"""JAX's persistent compilation cache for the launchers (examples,
``chip_smoke.py``).

A cold process on the chip otherwise recompiles every step program.  The
cache lives where ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads the variable
itself), else at the fixed path ``<repo>/.jax_cache``: the path is part of
the cache key, so it never comes from a temp dir, a pid or the time.  Tests
leave the cache alone.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
