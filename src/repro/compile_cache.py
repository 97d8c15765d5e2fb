"""Where the entry points keep JAX's persistent compilation cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there, and nothing is changed.  Otherwise the cache goes to ``.jax_cache/``
at the root of the checkout: a fixed path, because a later run finds a
cached program only under the same directory.  Tests do not call this.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Enable the persistent cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
