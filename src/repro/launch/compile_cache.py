"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``repro.launch.train``, the kernel bench)
call :func:`use_compile_cache` first thing in ``main``; importing this
module changes nothing.  A fixed directory matters: the path is part of the
cache key, so a directory that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Keep compiled programs across processes; return the cache directory.

    ``JAX_COMPILATION_CACHE_DIR`` set in the environment wins — JAX already
    reads it, so nothing is changed.  Otherwise the cache goes to the
    repository's git-ignored ``.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
