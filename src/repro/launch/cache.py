"""Persistent XLA compilation cache, placed from outside the program.

``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting: when it is set, JAX
reads it and this module sets nothing. Otherwise the cache goes to
``.jax_cache`` at the repository root. That path is fixed on purpose:
the directory is part of what a later run looks up, so a path built
from a temporary name, a PID or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.
    Call once, before the first compile of the process."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
