"""JAX's persistent compilation cache for the launchers and `chip_smoke.py`.

A fresh process recompiles every kernel; the persistent cache lets the next
process on the same machine load them instead.  Call
`enable_persistent_cache()` once, before the first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache — a fixed path, because the cache directory is part
# of each entry's key: a directory that moves never hits.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already keeps the cache
    there and nothing is set here.  Otherwise the cache goes to
    `DEFAULT_CACHE_DIR`, with every compile cached: the small GA kernels
    compile faster than JAX's default one-second threshold."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return str(DEFAULT_CACHE_DIR)
