"""Where JAX keeps its persistent compilation cache for this repo's entry
points (the serve launcher, the benchmark runner and chip_smoke.py)."""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    A `JAX_COMPILATION_CACHE_DIR` set in the environment is left to JAX;
    otherwise the cache lives in `<repo>/.jax_cache`.  The path is part of
    each entry's key, so it stays fixed rather than following the
    working directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
