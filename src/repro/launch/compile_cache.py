"""Where JAX keeps its persistent compilation cache.

``enable_compile_cache()`` is called once at the start of a program that
compiles for the chip (``chip_smoke.py``, ``repro.launch.serve``), never at
import. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing
is changed here. Otherwise the cache goes to ``<checkout>/.jax_cache``:
a fixed path, because the path is part of the cache key, so a directory
that moves from run to run never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if where:
        return where
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
