"""Persistent compilation cache for the entry points.

``chip_smoke.py``, :mod:`repro.launch.train` and the benchmark harness
call :func:`enable_compile_cache` once, before they compile anything.
Tests never do: a test run compiles for the CPU, and a described chip
cannot read back what it would write.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed and inside the checkout: the program writes nothing outside it
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else at
    :data:`REPO_CACHE_DIR`.  Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
