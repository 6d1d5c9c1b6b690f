"""JAX's persistent compilation cache, kept at a fixed place.

Entry points call :func:`use_compile_cache` before anything compiles, so a
second run of the same program loads its executables instead of compiling
them again. The cache's path is part of what a lookup matches, so it never
moves between runs: it is ``JAX_COMPILATION_CACHE_DIR`` where that is set
(JAX reads the variable itself), and otherwise ``.jax_cache/`` at the root of
the checkout this module lives in.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
