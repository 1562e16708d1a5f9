"""Where JAX keeps its persistent compilation cache.

Every entry point calls ``use_compile_cache()`` before its first
compile.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
it and nothing else is set here.  Otherwise the cache goes to
``<checkout>/.jax_cache``: a fixed path, because the path is part of
the cache key, so a directory named after a process or a time would
never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
#: the checkout's own cache directory (listed in .gitignore)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX at the persistent cache; returns the directory used."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
