"""JAX's persistent compilation cache, placed from outside the library.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.train``) call :func:`enable_compile_cache` before their
first compile; importing the library never does, so test runs keep JAX's
defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the fixed in-repo location (listed in .gitignore); never a temp name, a
#: pid or a time, so a later run finds what an earlier run stored
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing is changed here; otherwise the cache goes to
    :data:`REPO_CACHE_DIR`."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
