"""JAX persistent compilation cache: one directory rule for every entry
point (the serve/train launchers, the serve benchmark, chip_smoke.py).

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
module sets no other directory.  Otherwise the cache goes to one fixed
path inside the checkout, ``<repo>/.jax_cache`` (gitignored): the path
is part of the cache key, so it never depends on a temporary name, a
process id or the time.  Call :func:`enable_compile_cache` before the
first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
