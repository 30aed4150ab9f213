"""Where JAX keeps its persistent compilation cache for a run of this repo.

Called by entry points (``chip_smoke.py``, ``examples/graph_analytics.py``),
never when the library is imported.
"""
from __future__ import annotations

import os

import jax

__all__ = ["use_compile_cache"]

# <checkout>/.jax_cache: a fixed path, because the cache directory is part of
# what a later run must find again (listed in .gitignore)
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
    is set here; otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    return _CHECKOUT_CACHE
