"""JAX's persistent compilation cache, for the repo's entry scripts.

``chip_smoke.py`` and ``benchmarks/run.py`` call :func:`enable` before
their first compile, so a second run on the same machine reads its
programs back instead of compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# fixed, inside the checkout: a later run finds what an earlier one wrote
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to ``DEFAULT_DIR``.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
