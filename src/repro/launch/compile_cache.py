"""JAX's persistent compilation cache, at one fixed place per checkout.

Every entry point that compiles a whole step (``launch/serve.py``,
``launch/train.py``, ``chip_smoke.py``) calls :func:`enable` before its
first compile, so repeated runs of the same program reuse the compiled
executables instead of paying the compile again.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache. The path is part of every cache key, so it is
# fixed: never derived from a temporary name, a process id or the time.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to the checkout's
    ``.jax_cache`` directory (listed in ``.gitignore``)."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
