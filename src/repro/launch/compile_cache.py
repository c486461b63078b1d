"""JAX's persistent compilation cache, for the entry points.

Entry points (``launch/serve.py``, ``launch/train.py``, ``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before they
compile anything; library modules never set the cache.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is set
here.  Otherwise the cache lives at a fixed path inside the checkout,
``<repo>/.jax_cache`` (git-ignored): the directory is part of what a later
run must find, so it never comes from a temporary name, a process id or the
time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
