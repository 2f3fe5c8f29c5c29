"""JAX's persistent compilation cache for the entry points.

Each entry point (``chip_smoke.py``, ``python -m repro.bench run``,
``repro.launch.serve``, ``repro.launch.train``) calls
:func:`enable_compile_cache` once before it compiles; importing this module
changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the environment variable JAX itself reads the cache directory from
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root (this file is ``<checkout>/src/repro/launch/...``)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing is set in code. Otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache``: the directory is part of each entry's
    key, so a path that moved between runs would never hit.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
