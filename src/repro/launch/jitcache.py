"""JAX's persistent compilation cache, as every entry point sets it up.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
nothing is set here. Otherwise the cache lives at one fixed path inside
the checkout, ``<repo>/.jax_cache`` (gitignored). The path is fixed
because a cache that moves never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it writes to."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
