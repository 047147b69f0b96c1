"""Where JAX keeps its persistent compilation cache.

Call ``setup_compile_cache()`` from a program's ``main()``, before its first
compile; never at import.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins:
JAX reads it itself and nothing here overrides it.  Otherwise the cache goes
to ``<repo>/.jax_cache``, a fixed path (the path is part of the cache key,
so a directory that moved between runs would never hit).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return REPO_CACHE_DIR
