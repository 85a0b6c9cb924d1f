"""JAX's persistent compilation cache, placed from outside the program.

A cold serve engine compiles one prefill executable per shape bucket plus
decode, verify, page-copy and scrub; at published widths each compile
takes tens of seconds.  Entry points (``repro.launch.serve``,
``benchmarks/run.py``, ``chip_smoke.py``) call :func:`enable_compile_cache`
from their ``main()`` so repeated runs on one machine skip them.  Nothing
turns the cache on at import, and the tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

#: The checkout's own cache directory (listed in ``.gitignore``).  Fixed,
#: never derived from a temporary name, a process id or the time: a cache
#: whose directory moves between runs never hits.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already taken the
    directory from it and nothing is set here.  Otherwise the cache goes
    to :data:`REPO_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
