"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, ``examples/``, ``benchmarks/``) call
``enable_compile_cache()`` once before they compile; nothing calls it at
import, and the tests never do.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets no other directory.  Otherwise the cache lives at ``<repo>/.jax_cache``
(git-ignored): a fixed path, because the path is part of what a later
process must find again — never a temp name, PID or timestamp.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """Where the cache goes: ``JAX_COMPILATION_CACHE_DIR``, else the
    checkout's ``.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
