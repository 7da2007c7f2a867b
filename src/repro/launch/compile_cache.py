"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points (``chip_smoke.py``, ``launch.serve``, ``launch.train``)
call :func:`enable` once at start-up — never at import — so a run that
compiles the same programs again (the next chip call on a machine that
keeps the checkout, a second launcher in one session) reads them back
instead of recompiling.  A later run finds the cache only at the same
path, so the path is fixed: ``<checkout>/.jax_cache``, never a
temporary, pid- or time-derived one.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the cache on and return its directory.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing else; otherwise the cache goes to ``<checkout>/.jax_cache``."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
