"""Placement of JAX's persistent compilation cache for the entry points.

`enable_compile_cache` is called from the `main()` of each entry point
(`chip_smoke.py`, `launch/serve.py`, `benchmarks/run.py`), never at import.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
other path is set here.  Otherwise the cache lives at the fixed path
``<repo root>/.jax_cache``: the path is part of the cache key, so a
temporary or per-process directory would never hit.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
