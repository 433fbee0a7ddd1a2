"""JAX persistent compilation cache for the repo's entry points.

`enable_compile_cache()` is called by `chip_smoke.py` and
`benchmarks.sim_bench` before their first compile; no library module calls
it, so importing `repro` never turns on a cache.

Where the cache lives:
  - ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it at import, and
    the helper leaves that directory alone (sets no other).
  - unset: ``<repo root>/.jax_cache``, found from this file's path.  The
    directory is part of the cache key, so it must not depend on a
    temporary name, a process id or the time, or no later run would hit.

The playback programs compile in well under JAX's default 1 s threshold,
so the minimum compile time to persist is set to 0.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
