"""Persistent XLA compilation cache for entry-point scripts.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, after importing jax and before their
first compile; library code never does.  This module imports nothing
from jax at import time.

* ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it; nothing else
  is configured.
* otherwise: a fixed ``<checkout>/.jax_cache`` (listed in .gitignore).
  The cache path is part of what makes an entry hit, so it never depends
  on a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache"]

_CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
