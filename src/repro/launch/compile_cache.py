"""Where JAX's persistent compilation cache lives.

The cache is keyed by, among other things, its own path, so a directory
that moves between runs never hits. Entry points call
:func:`use_compile_cache` once, before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and wins;
    nothing is set in code then. Otherwise the cache goes to ``.jax_cache/``
    at the root of this checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
