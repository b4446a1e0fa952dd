"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/*.py``, ``python -m
repro.launch.serve``) call :func:`enable_compile_cache` once before their
first compile; importing the library never does.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
else is set.  Otherwise the cache sits at a fixed path inside the
checkout (``<repo>/.jax_cache``, gitignored): the directory is part of
what a later run must find again, so it never comes from a tempdir, a
pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<repo>/.jax_cache`` for a source checkout (``src/repro/<this file>``).
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
