"""JAX's persistent compilation cache for the command-line entry points.

A cold process compiles every program again; the persistent cache lets the
next process on the same machine load them instead.  The entry points call
`enable_compile_cache` from their ``main()``; importing this module changes
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

# a fixed path: a cache directory that moves between runs never hits
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on the persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing; otherwise the cache lives in ``<repo>/.jax_cache``.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
