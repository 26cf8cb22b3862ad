"""Where JAX keeps its persistent compilation cache.

Entry points (``repro.launch.train``, ``repro.launch.serve``,
``chip_smoke.py``) call :func:`enable_compile_cache` from their
``main()``; no module calls it on import.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and no other
  directory is set here.
* Otherwise the cache sits at ``<repo root>/.jax_cache``.  The path is
  fixed (no temp name, pid or time in it) because it is part of what a
  later run looks up.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
