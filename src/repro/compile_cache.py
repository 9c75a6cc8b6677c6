"""Where the entry points keep JAX's persistent compilation cache.

A cold sweep spends most of its time compiling one runner per padded
shape, so entry points (`chip_smoke.py`, `benchmarks.run`, the
`benchmarks.*_bench` mains) call `use_compile_cache()` before their first
compile.  The library never calls it on import, so tests and library
users keep JAX's own default.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed in-checkout location (gitignored): a cache is found again only
#: at the same path, so it is never built from a temp name, pid or time
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    If JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and no
    other directory is set here; otherwise the cache goes to CACHE_DIR.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
