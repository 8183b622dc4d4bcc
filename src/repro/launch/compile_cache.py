"""Where JAX keeps its persistent compilation cache for the entry points.

A cold run of a 32-layer step recompiles every program; the persistent
cache makes the next run on the same machine skip that.  JAX reads
`JAX_COMPILATION_CACHE_DIR` itself, so when it is set nothing is changed
here.  Otherwise the cache goes to `.jax_cache/` at the root of the
checkout: a fixed path, because the path is part of the cache key and a
directory that moves never hits.
"""
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
