"""Persistent XLA compilation cache for the serving entry points.

Megatick executables are while_loops over the full tick body, so their
compiles are the most expensive in the repo; with the cache on, every
process after the first starts serving at full tick rate.

The cache lives where ``$JAX_COMPILATION_CACHE_DIR`` says when it is set,
and otherwise in ``.xla_cache/`` at the root of the checkout (listed in
``.gitignore``): one fixed directory, so a later process finds what an
earlier one wrote.  Only entry points (``launch/serve.py``,
``chip_smoke.py``) call :func:`ensure_compilation_cache`, before their
first compile; library code and tests never arm it.
"""
from __future__ import annotations

import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".xla_cache")


def ensure_compilation_cache() -> str:
    """Point jax's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` or, when unset, the checkout's
    ``.xla_cache/``.  Call before the first compile; returns the dir."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or CHECKOUT_CACHE_DIR
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # smoke-scale ticks compile in well under the default 1s floor; cache
    # them anyway — the point is cold-start tick rate, not disk
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
