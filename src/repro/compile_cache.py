"""JAX's persistent compilation cache, placed from outside or at a
fixed path in the checkout.

Entry points call :func:`enable_compile_cache` first thing; nothing
calls it at import time, and the tests never do.  The cache key includes
the directory, so the fallback path is fixed — ``<checkout>/.jax_cache``,
derived from this package's location — never a temporary name, a pid or
a time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache, and JAX reads
    it by itself: no other directory is set.  Otherwise the cache lives
    in ``<checkout>/.jax_cache`` (listed in ``.gitignore``)."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
