"""Persistent compile cache at a fixed place.

Compiling the engine's while-loop and the router's training iteration
takes seconds to minutes, and the cache key includes the cache path, so
a path that moves never hits.  ``configure`` keeps it in one fixed
directory per checkout unless the caller's environment already names one.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def configure() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed; otherwise the cache goes to ``<checkout>/.jax_cache``
    (listed in ``.gitignore``).  Call before the first compile."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
