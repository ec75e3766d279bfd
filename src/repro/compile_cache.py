"""Where JAX keeps its persistent compilation cache for this repo's runs.

Call :func:`enable_compile_cache` once at the start of an entry point
(``chip_smoke.py``, ``benchmarks/run.py``), never on import.  The cache's
directory is part of what makes an entry found again, so it is fixed: the
``JAX_COMPILATION_CACHE_DIR`` environment variable when set, otherwise
``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
