"""JAX's persistent compilation cache: one rule for every entry point.

Compiling a 24-layer serving step once per chunk length can dominate a
cold run, so the scripts (`chip_smoke.py`, `benchmarks/paged_serving.py`,
`examples/*`) call `enable_compile_cache()` before their first compile.
The cache directory is part of what makes an entry hit, so it is fixed:
never a temp name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache — listed in .gitignore.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to `CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
