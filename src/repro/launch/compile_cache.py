"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``launch/serve.py``)
call :func:`enable` before their first compilation, so a second process on
the same checkout loads its executables instead of compiling them again.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: fixed, because the path is part of what a later
# process must find again (git ignores it)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and nothing
    else is set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
