"""JAX's persistent compilation cache for the entry points that drive a chip.

A run on a fresh machine compiles every program it dispatches; the
persistent cache lets later processes of the same checkout load them
instead. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing here overrides it. Otherwise the cache lives in the fixed
``.jax_cache/`` at the checkout root (git ignores it): a fixed path,
because the directory is part of what a later run must find again.

Entry points call :func:`enable_compile_cache` at start-up, never at
import, so tests that import them leave the cache off.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``src/repro/launch/compile_cache.py`` -> the checkout root
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
