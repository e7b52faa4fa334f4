"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/serve_queries.py``) call
``enable_compile_cache()`` before they compile anything; tests never
do.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set → JAX already reads it, and no other
  directory is set;
* otherwise → ``<checkout>/.jax_cache``, a fixed path (the path is part
  of what a later run must find again), listed in ``.gitignore``.

Every compile is cached, not only those past JAX's one-second default:
a tick of the served path compiles many small programs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

_DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
