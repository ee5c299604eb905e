"""JAX's persistent compilation cache for the entry points.

Called by the entry points (chip_smoke.py, launch/train.main,
examples/serve_batch.py), never at import: tests and library callers keep
JAX's defaults.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache: a fixed path, because the directory is part of what
# a later run must find again (listed in .gitignore)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this
    sets no other directory; otherwise the cache goes to DEFAULT_DIR."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
