"""Where JAX keeps its persistent compilation cache."""
from __future__ import annotations

import os
import pathlib

from jax.experimental.compilation_cache import compilation_cache

# <checkout>/.jax_cache: a fixed path, so every run from this checkout
# finds what an earlier run compiled
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it at import and
    this changes nothing.  Otherwise the cache goes to
    `CHECKOUT_CACHE_DIR`.  JAX decides once per process whether the cache
    is in use, so the decision is reset here: a caller that compiled
    before turning the cache on still gets it for what it compiles next.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    compilation_cache.set_cache_dir(str(CHECKOUT_CACHE_DIR))
    compilation_cache.reset_cache()
    return str(CHECKOUT_CACHE_DIR)
