"""JAX's persistent compilation cache, placed from outside the code.

Call :func:`enable_compile_cache` from a program's ``main`` (never at
import).  Where ``$JAX_COMPILATION_CACHE_DIR`` is set, the cache lives
there and nowhere else; otherwise at the fixed path ``<repo>/.jax_cache``
(listed in ``.gitignore``).  The path is part of the cache key, so a
directory that moved between runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
