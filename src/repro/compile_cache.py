"""JAX's persistent compilation cache, shared by every entry point
(`chip_smoke.py` and the benchmark drivers).

The cache lives at `JAX_COMPILATION_CACHE_DIR` when that is set and at
the fixed `<repo>/.jax_cache` otherwise (gitignored).  The directory is
part of the cache key, so it never moves and no other path is set in
code.  Thresholds drop to zero so the many small scheduler programs are
cached too: a warm cache turns repeat runs into pure execution.
"""
from __future__ import annotations

import os

_DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", ".jax_cache"))


def enable_compilation_cache() -> str:
    """Turn the persistent compilation cache on before the first
    compile; returns its directory."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", _DEFAULT_DIR)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir
