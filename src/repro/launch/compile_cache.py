"""JAX's persistent compilation cache for the entry points.

Call :func:`enable_compile_cache` from a ``main`` (never at import): a
second run of the same program then loads its compiled steps from disk
instead of compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

# The checkout this package runs from (src/repro/launch -> repo root). Only
# a source or editable checkout has its pyproject.toml there; an installed
# copy lives under site-packages, which is no place for a cache.
_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already and
    it stands. Otherwise the cache goes to the fixed ``.jax_cache/`` at the
    root of the checkout; outside a checkout it stays off (``None``).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if not (_ROOT / "pyproject.toml").is_file():
        return None
    cache = str(_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    return cache
