"""Where JAX keeps its persistent compilation cache for this checkout.

A cold TPU process compiles every step program again; a persistent cache
lets the next process on the same machine skip that.  The cache key
includes the directory, so the directory must not move between runs: a
temporary, pid- or time-derived path would never hit.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it at import, so
  nothing is set here — whoever placed the cache owns every setting.
* Otherwise: ``<checkout>/.jax_cache``, a fixed path (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable directory and
    return it.  Idempotent: a second call changes nothing."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    if jax.config.jax_compilation_cache_dir != CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
