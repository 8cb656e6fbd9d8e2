"""JAX's persistent compilation cache, kept at one fixed path.

Entry points call :func:`enable_compile_cache` once at start-up
(``chip_smoke.py``, ``benchmarks/run.py``); importing ``repro`` never does.
The cache directory is part of what a hit is keyed on, so it never moves:
``JAX_COMPILATION_CACHE_DIR`` where that is set (JAX reads it itself),
otherwise ``<repo root>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

#: the default cache directory: ``.jax_cache`` beside ``src/``.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every program this process
    compiles, the sub-second tuner sweeps included; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
