"""JAX's persistent compilation cache, switched on by the entry points.

``enable_compile_cache()`` is called by ``chip_smoke.py``,
``python -m repro.launch.serve`` and ``python -m benchmarks.run`` before
they compile anything; importing the package never touches the cache.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads the directory from it
and this sets no other.  Otherwise the cache lives at ``.jax_cache/`` in
the repository root: a fixed path, because the path is part of each
entry's key.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile; returns its path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
