"""Persistent XLA compile cache, set in one place for every entry point.

A cold start on a TPU compiles every ShufflePlan and eager op it touches;
the persistent cache lets the next process on the same checkout skip that.
Entry points that compile (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` before their first compile.

A later process finds an entry only under the same directory, so the
directory is fixed: the ``JAX_COMPILATION_CACHE_DIR`` environment variable
when it is set (JAX reads it itself; nothing is set in code), otherwise
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its fixed directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
