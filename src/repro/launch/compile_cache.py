"""JAX's persistent compilation cache at one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
nothing here overrides it.  Otherwise the cache lives in ``.jax_cache``
at the root of the checkout: a fixed path, since the path is part of the
cache key and a directory that moves never hits.  The launchers and
``chip_smoke.py`` call :func:`enable_compile_cache` before any compile.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the in-checkout default (listed in ``.gitignore``)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
