"""JAX's persistent compilation cache for the entry points.

``chip_smoke.py``, ``repro.launch.sge_run`` and ``repro.launch.serve`` call
:func:`enable_compile_cache` first thing in ``main``; importing the library
changes nothing.  ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as
JAX reads it itself.  Otherwise the cache lives at a fixed directory inside
the checkout — the path is part of the cache key, so a directory that moved
between runs would never hit.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
