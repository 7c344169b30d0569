"""Persistent compilation cache for the entry points.

Called from ``main()`` of each launcher and from ``chip_smoke.py``, never on
import.  ``JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the
cache lives at one fixed directory of the checkout (``.jax_cache/``, git
ignores it).  The path is part of the cache key, so it must not move.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(REPO_ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
