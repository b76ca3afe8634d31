"""One persistent XLA compile cache per checkout.

Every entry point — ``repro.launch.dse``, each fleet worker,
``repro.launch.recommend`` / ``repro.launch.serve`` and ``chip_smoke.py``
— calls :func:`enable_compile_cache` before its first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache and the
program names no other.  Otherwise the cache is ``<checkout>/.jax_cache``:
a fixed path, never one under a run directory, a temp name, a pid or a
time, so a later process of the same checkout finds what an earlier one
compiled.  Fleet workers run from the same checkout with the parent's
environment, so they share the parent's cache.
"""
from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``: the checkout is the parent of the
    ``src/`` directory this package is imported from."""
    import repro
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    return os.path.join(os.path.dirname(src), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on jax's persistent compile cache (every compile is cached,
    however small or quick) and return its directory."""
    import jax
    path = os.environ.get(CACHE_DIR_ENV) or default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
