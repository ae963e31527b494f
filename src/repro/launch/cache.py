"""JAX's persistent compilation cache for the entry points.

Compiling the serving programs of a full-width model takes minutes;
the cache turns every later process's compile of the same program into
a load.  Its directory is part of what makes an entry findable again,
so it is a fixed path, never a temporary or per-process one.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (``<repo>/src/repro/launch/cache.py``)
REPO_ROOT = Path(__file__).resolve().parents[3]

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other path is set here.  Otherwise the cache lives in
    ``<repo>/.jax_cache`` (listed in ``.gitignore``).
    """
    path = os.environ.get(ENV_CACHE_DIR)
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
