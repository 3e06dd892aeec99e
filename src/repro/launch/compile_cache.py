"""JAX's persistent compile cache, placed by the command-line entry points.

The entry points (``chip_smoke.py``, ``python -m repro.launch.train``,
``examples/serve_batched.py``) call :func:`enable_compile_cache` before
they compile; library modules and tests never do.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
changed.  Otherwise the cache goes to a fixed directory inside the
checkout: the path is part of each entry's key, so a directory that
moved would never hit."""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Make sure the persistent compile cache is on; returns its
    directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
