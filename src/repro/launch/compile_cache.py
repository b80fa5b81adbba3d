"""JAX's persistent compilation cache for the entry points.

Called from ``main()`` of the launchers and from ``chip_smoke.py``, never on
import: a library that sets a process-wide cache would surprise its callers.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# one fixed directory in the checkout: a cache entry's key includes the
# path, so a per-process or temporary directory would never be hit again
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep compiled programs across processes.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
    set here; otherwise the cache goes to ``CACHE_DIR``."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
