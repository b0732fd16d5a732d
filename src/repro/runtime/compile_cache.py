"""JAX's persistent compilation cache, for the program's entry points.

A chip run compiles every program cold unless the cache directory
survives between processes. The directory is part of each entry's key,
so it must be a fixed path: never one built from a temporary name, a
pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout this package runs from (``<checkout>/src/repro/runtime``)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own reading of it
    stands and nothing is changed. Otherwise the cache goes to
    ``<checkout>/.jax_cache``. Call from an entry point's ``main`` only,
    never on import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
