"""JAX's persistent compilation cache, placed from outside or at one fixed
path inside the checkout.

Call :func:`enable_compile_cache` from an entry point's ``main`` (never at
import time): a later process with the same programs then loads them from
disk instead of compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (listed in .gitignore). Fixed, never a temp, pid or
# time-based path: the directory is part of what a later run looks up.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

# JAX skips programs that compiled in under a second by default. A run of
# the engine compiles one large scan plus dozens of small eager and
# data-synthesis programs; caching those too is what makes a warm start
# cheap, so every program is cached.
MIN_COMPILE_SECS = 0.0


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads
    the variable itself, and no other directory is set in code). Otherwise
    the cache lives at :data:`DEFAULT_DIR`."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    return path
