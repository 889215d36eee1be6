"""Where JAX keeps its persistent compilation cache.

A 40-layer model compiles its prefill and decode steps for tens of
seconds each; the persistent cache lets a later run of the same program
load them instead.  Entry points call :func:`enable_compile_cache` once,
before their first compile.  Nothing here runs on import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (git-ignored).  A fixed path: the cache is
#: keyed by it, so a directory named after a pid or a time never hits.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as is: JAX reads
    it itself and this sets no other.  Otherwise the cache goes to
    :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
