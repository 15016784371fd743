"""The persistent XLA compile cache, set once by each entry point.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, because the path is part of the cache key.  Entry points
(``chip_smoke.py``, ``repro.launch.train.main``, ``benchmarks/run.py``)
call :func:`enable_compile_cache`; importing a module never does.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the checkout's own cache directory (listed in .gitignore)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
