"""JAX's persistent compilation cache for the repo's entry points.

Every entry point's ``main`` calls :func:`enable_compile_cache` before it
compiles anything, so the processes of one run share compiled programs (each
new process would otherwise recompile every jitted step and kernel). Nothing
here runs at import time.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The checkout's own cache, used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
#: A fixed path: the directory is part of the cache's key, so a name that
#: changed from run to run would never hit.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing. Otherwise the cache goes to :data:`CHECKOUT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
