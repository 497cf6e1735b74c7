"""Where JAX keeps its persistent compilation cache.

Called at program start-up (``chip_smoke.py``, ``repro.launch.serve``),
never on import.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache: src/repro/launch/compile_cache.py is three levels in
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads it
    itself) and no other directory is set.  Otherwise the cache is the
    fixed, git-ignored ``.jax_cache`` of the checkout: a run finds what an
    earlier run from the same checkout compiled only if the path stays put.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    return _CHECKOUT_CACHE
