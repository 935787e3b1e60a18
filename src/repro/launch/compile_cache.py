"""JAX's persistent compilation cache, placed once for every entry point."""
from __future__ import annotations

import os
from pathlib import Path

# fixed, git-ignored, inside the checkout: the cache directory is part of
# an entry's key, so a path that moved between runs would never hit
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and JAX already
    reads it; otherwise the cache lives at :data:`CHECKOUT_CACHE_DIR`.
    Every compile is cached, however short or small, so a second process
    of the same configuration starts warm.  Call before the first compile.
    """
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
