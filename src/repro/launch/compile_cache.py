"""Where JAX's persistent compilation cache lives.

Call :func:`enable` from a program's ``__main__`` (never on import).  If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there
and nothing is changed.  Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout: a fixed path, because the path is part of what a
cached entry is found by, and git-ignored.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
