"""JAX's persistent compilation cache for the repo's entry points.

Called by ``chip_smoke.py``, ``benchmarks/run.py`` and ``examples/*.py``
before their first compile — never at library import, so importing
``repro`` configures nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the one fixed cache directory inside the checkout (listed in .gitignore);
#: JAX keys cache entries by path, so it must not move between runs
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is configured. Otherwise the cache lives at
    :data:`REPO_CACHE_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
