"""JAX's persistent compilation cache for the launch entry points.

Called by ``launch/train.py:main`` and ``chip_smoke.py`` before their first
compile, so a re-run of the same program loads its executables instead of
compiling them again.  Library code and tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

# fixed, checkout-relative: the cache directory is part of what a cached
# entry is found by, so it is never made from a temporary name, a pid or
# the time
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache lives in ``.jax_cache/``
    at the checkout root.  Returns ``None`` (and sets nothing) where the
    cache was switched off with ``JAX_ENABLE_COMPILATION_CACHE=false``."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
