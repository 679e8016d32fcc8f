"""Where JAX's persistent compilation cache lives — one rule for the
whole program (entry points, examples and the ``procs`` fleet).

  * an explicit directory (``ProcsEngine(cache_dir=...)``, a benchmark's
    scratch cache) wins;
  * else, if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing here sets another directory;
  * else the cache is ``<checkout>/.jax_cache`` — a fixed path, because the
    path is part of what lets a later process hit an earlier compile.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache(explicit: str | None = None) -> str:
    """Turn the persistent cache on at the directory the rule above picks
    and return it.  Every compile is cached, however small or quick: the
    ``procs`` fleet's prebuilt granule steppers are small but compiled by
    every worker."""
    path = explicit or os.environ.get(ENV) or CHECKOUT_CACHE
    os.makedirs(path, exist_ok=True)
    if explicit or not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
