"""Where the entry points keep JAX's persistent compilation cache.

The cache key includes the directory, so it must not move between runs:
a path built from a temp name, a pid or the time would never hit.  When
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is
set here; otherwise the cache lives at a fixed directory inside the
checkout (``.jax_cache/``, git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: the checkout's own cache directory (``<repo>/.jax_cache``)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache for this process and
    return its directory.  Call once from an entry point, before the
    first compile.

    Example::

        from repro.launch.compile_cache import enable_compile_cache
        print("compile cache:", enable_compile_cache())
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
