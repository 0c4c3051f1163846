"""The persistent XLA compilation cache of every entry point.

The cost kernels take seconds each to compile, and a fleet compiles
several, so every process that serves or measures searches calls
:func:`enable_compile_cache` before its first compile:

* with ``JAX_COMPILATION_CACHE_DIR`` set, the cache lives there and no
  other directory is configured;
* otherwise it lives in ``<checkout>/.jax_cache`` — a fixed path derived
  from this file, never from the working directory, a pid or the time,
  so a later process finds what an earlier one wrote.

Every compile is cached, however short or small.
"""
from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see
    the module docstring) and cache every entry; returns the path."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
