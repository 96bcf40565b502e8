"""Persistent XLA compilation cache for the command-line entry points.

A TPU program at real widths takes tens of seconds to compile; the cache
lets a later process (the next run of the same command) load it instead.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    nothing else is set here. Otherwise the cache is ``<checkout>/.jax_cache``
    (listed in ``.gitignore``): a fixed path, because a directory that
    moves between runs never hits. Call it from an entry point's main,
    never while a module is imported: tests that compile for a described
    chip must not write entries that only a chip can read."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
