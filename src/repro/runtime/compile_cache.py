"""Persistent XLA compilation cache placement for the command-line tools.

Call :func:`enable` at the start of an entry point's ``main`` (never at
import time). Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here; otherwise the cache goes to one fixed
directory in the checkout, ``<repo>/.jax_cache`` (git-ignored). The path
is part of each entry's key, so it never moves between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<repo>/.jax_cache`` — this file lives at ``<repo>/src/repro/runtime/``.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
