"""JAX's persistent compilation cache for the repo's entry points.

Scripts, benchmarks and examples call :func:`enable_compile_cache` once,
before their first compile; the library itself never turns the cache on
(importing ``repro`` changes no JAX configuration).
"""

from __future__ import annotations

import os
import pathlib

#: ``<checkout>/.jax_cache`` — fixed by the package location, so repeated
#: runs from one checkout share compiled programs (the path is part of
#: the cache key; a directory that moves never hits).
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed; otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
