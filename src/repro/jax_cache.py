"""Where JAX keeps its persistent compilation cache.

Entry points (``launch/train.py``, ``core/selfcheck.py``, ``chip_smoke.py``)
call :func:`enable_compile_cache` before their first compile.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is set
here.  Otherwise the cache goes to ``.jax_cache/`` at the checkout root: a
fixed path, because the path is part of the cache key and a directory that
moves never hits.
"""
from __future__ import annotations

import os
import pathlib

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/``)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
