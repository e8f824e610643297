"""Persistent compilation cache for the repo's scripts.

A cold device run spends a large share of its time compiling the
engine's while-loop (over a minute at a 2^20-event capacity on a TPU
v5e).  Scripts call :func:`use_compile_cache` once in ``main()`` so
repeated runs from one checkout reuse compiled programs.  The library
never calls it on import, and the tests never call it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent cache at a fixed directory; returns it.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed.  Otherwise the cache lives at
    ``<checkout>/.jax_cache`` (git-ignored): a fixed path, because the
    path is part of the cache key and a moving directory never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
