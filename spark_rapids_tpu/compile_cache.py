"""Where the persistent XLA compilation cache lives.

The chip's compiler takes tens of seconds to minutes for the engine's
sort and scan programs at real batch sizes (CHANGES.md, PR 21), so a
cold run of a join/sort query is mostly compilation and the cache
decides whether a chip run takes minutes or a quarter of an hour. The
cache directory is part of the cache key, so it is never a temp name, a
pid or a timestamp — and it is placed from OUTSIDE when the caller asks:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  this module sets no directory in code (in particular it never
  overrides it);
- otherwise: the fixed, git-ignored ``<checkout>/.bench_cache/xla``.

``chip_smoke.py`` calls :func:`enable_compile_cache`; the benchmark
places its own cache at the same path (``benchmark/run.py``).
"""
from __future__ import annotations

import os

__all__ = ["CHECKOUT", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

#: the directory holding the package (the repo root in a checkout)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT, ".bench_cache", "xla")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in
    use (the environment's when it names one)."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
