"""Where JAX's persistent compilation cache lives.

One rule for every entry point (chip_smoke.py, tools/*.py, the hardware
test tier): the cache is placed from outside through
JAX_COMPILATION_CACHE_DIR, which JAX reads itself; only where that is not
set does code name a directory, and then always the same one, because the
path is part of the cache key and a directory that moves never hits."""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Return the cache directory in force, setting
    `<checkout>/.jax_cache` only if JAX_COMPILATION_CACHE_DIR is unset."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
