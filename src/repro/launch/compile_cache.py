"""Persistent XLA compilation cache at a fixed path.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set, that
directory is the cache and nothing here overrides it. Otherwise the
cache goes to ``<checkout>/.jax_cache`` (listed in ``.gitignore``). The
directory is part of what a later run looks the cache up by, so it is
fixed: never a temporary, per-process or per-run path.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before anything
    compiles; returns the directory it writes to."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
