"""Where JAX keeps its persistent compilation cache.

A full-width diffusion stage takes minutes to compile, and a cache only
hits where it was written: the directory is part of the cache key.  So it
lives at a fixed place.  Call :func:`configure_compile_cache` before the
first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/src/repro/launch/cache.py -> <checkout>/.jax_cache
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it.  A ``JAX_COMPILATION_CACHE_DIR`` set in the environment is
    JAX's own setting and is left alone; otherwise the cache goes to
    ``.jax_cache/`` at the root of this checkout."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
