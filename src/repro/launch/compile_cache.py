"""Where JAX keeps its persistent compilation cache for this checkout.

Every entry point calls ``enable_compile_cache()`` before its first
compile.  A ``JAX_COMPILATION_CACHE_DIR`` set by the environment wins (JAX
reads it itself, nothing is set here); otherwise the cache lives at the
fixed ``<checkout>/.jax_cache`` — the path is part of the cache key, so a
directory that moved between runs would never hit.
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))


def enable_compile_cache(checkout: str = CHECKOUT) -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
