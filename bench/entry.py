"""Process set-up shared by the benchmark's scripts (``run.py``, ``sweep.py``,
``calibrate.py``): the compile cache and the import path."""

from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CACHE = ROOT / ".jax_cache"


def prepare() -> str:
    """Point JAX's persistent compilation cache at ``.jax_cache`` in the
    checkout (a fixed path: the directory is part of the cache key; nothing is
    shared with another checkout), cache every program however fast it
    compiled, put the program's ``src`` on the path, and let the program's
    own helper see the cache. Call before JAX is imported."""
    CACHE.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"  # no eviction: the directory is this checkout's own
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    return enable_compile_cache()
