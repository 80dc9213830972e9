"""Persistent XLA compilation cache for entry points.

Call ``enable_compile_cache`` from a script's ``main`` (never at import):
a chip run otherwise compiles every program from scratch.
"""
from __future__ import annotations

import os

import jax

CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(root: str) -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache sits at ``<root>/.jax_cache``:
    a fixed path, so later runs from the same checkout find its entries.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
