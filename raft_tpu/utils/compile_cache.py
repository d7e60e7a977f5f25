"""Persistent XLA compilation cache.

Cold compiles dominate short runs on the chip (an index build's k-means
program alone compiles for tens of seconds and executes in under one), so
every runnable — ``chip_smoke.py``, ``bench.py``, the test suite and
``__graft_entry__`` — shares compiled executables across processes through
JAX's on-disk cache. Library code never enables it (user policy, like the
reference leaving cudaDeviceSetCacheConfig to apps).

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX
reads the variable itself, so nothing is set in code), otherwise one fixed
directory inside the checkout, :data:`DEFAULT_DIR` (gitignored). The path
is part of the cache key, so it never depends on a temp name, a pid or the
time.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_persistent_cache() -> str:
    """Turn on JAX's on-disk compilation cache; returns its directory."""
    import jax

    if os.environ.get(ENV):
        return os.environ[ENV]
    os.makedirs(DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
