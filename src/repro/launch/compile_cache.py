"""Where the program's entry points keep JAX's persistent compile cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, the benchmark
harness) call ``setup_compile_cache`` first thing; library imports never
do, so tests stay cache-free. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing is set here. Otherwise the cache goes to
``<repo root>/.jax_cache``: a fixed path, because the path is part of the
cache's key and a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Place the persistent compile cache; returns its directory."""
    outside = os.environ.get(ENV)
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
