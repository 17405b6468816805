"""Where JAX keeps its persistent compilation cache.

A cold run on a TPU compiles every program; the persistent cache lets the
next run in the same place skip that.  The cache's key includes its path,
so the path is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX
reads that variable itself, and nothing here overrides it), otherwise
``<repo>/.jax_cache``, which git ignores.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Call before the first compile."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
