"""Where the persistent XLA compile cache lives.

Launchers call ``enable_compile_cache()`` once at start-up (never at import,
never from tests).  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and nothing is set here.  Otherwise the cache goes to the fixed
``<repo>/.jax_cache`` (listed in ``.gitignore``): a cache only hits when its
directory stays put, so the path is never a temp, pid or time-based one.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
