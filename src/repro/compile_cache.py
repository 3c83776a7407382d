"""JAX's persistent compilation cache, at one fixed place.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``enable_compile_cache()`` before their first compile; importing the
package never does. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and this sets nothing. Otherwise the cache goes to
``.jax_cache/`` at the repository root: the directory is part of the
cache's key, so it is never built from a temporary name, a pid or the
time.
"""
from __future__ import annotations

import os
import pathlib

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
