"""JAX persistent compilation cache for the repo's entry points.

``enable_compile_cache()`` is called once at the start of each entry
point (``chip_smoke.py``, the benchmarks, the examples); importing a
library module never turns the cache on.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX itself reads it, so the cache
  lives there and nothing else is configured here.
* unset: the cache lives at a FIXED path inside the checkout,
  ``<repo>/.jax_cache`` (git-ignored).  The directory is part of each
  entry's lookup, so it is never derived from a temporary name, a
  process id or the time — a moving directory would never hit.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: repo root: src/repro/launch/cache.py -> three levels up from src/
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives for this process."""
    return os.environ.get(ENV) or os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Compiles of any duration are cached (the chip's cold start is what
    the cache exists to skip)."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
