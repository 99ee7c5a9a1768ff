"""Where the entry points keep JAX's persistent compilation cache.

`JAX_COMPILATION_CACHE_DIR`, when set, is read by JAX itself and
nothing is set here. Otherwise the cache lives at the fixed
`<repo>/.jax_cache` (gitignored): the directory is part of what a cache
entry is found by, so it never moves between runs. Only entry points
call this; tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
