"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``benchmarks/stream_throughput.py``) call ``enable_compile_cache()`` from
their ``main()``; nothing calls it at import, so the test suite never writes
to the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache lives at ``<repo>/.jax_cache`` (a fixed
    path, so a later run finds what an earlier one compiled).  The minimum
    compile time is lowered to zero so the small kernel and row-op programs
    are cached too, not only the programs that take a second to compile."""
    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
