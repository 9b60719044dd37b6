"""Persistent XLA compilation cache for the command-line entry points.

``enable_compile_cache()`` is called by ``chip_smoke.py`` and
``benchmarks/run.py`` before their first compile, never at library import.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and this sets no other directory.  Otherwise the cache lives at the
fixed path ``<checkout>/.jax_cache`` (listed in ``.gitignore``): the path
is part of what a later process must find again, so it never depends on a
temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache", "CHECKOUT_CACHE_DIR"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
