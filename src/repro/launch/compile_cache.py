"""JAX's persistent compilation cache at a fixed, placeable location.

``JAX_COMPILATION_CACHE_DIR`` set in the environment wins: JAX reads it
itself and this module sets nothing.  Otherwise the cache goes to
``<checkout>/.jax_cache`` (listed in ``.gitignore``) — a fixed path, because
the path is part of what a later run must find again; a temporary name would
never hit.  Call :func:`enable` first thing in an entry point, before any
compilation.
"""
from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Point JAX's persistent compilation cache somewhere stable; returns
    the directory in effect."""
    import jax
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
