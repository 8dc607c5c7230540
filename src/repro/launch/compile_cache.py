"""Persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*``)
call :func:`enable_compile_cache` once at start-up; importing the library
never does. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this sets nothing. Otherwise the cache lives at a fixed
``<repo>/.jax_cache`` (listed in ``.gitignore``), so a later run from the
same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root: src/repro/launch/compile_cache.py -> <repo>
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
