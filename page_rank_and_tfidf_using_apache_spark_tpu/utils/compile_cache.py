"""JAX's persistent compilation cache, kept at one fixed place.

The path is part of what JAX's cache can hit on, so it never moves: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and nothing is
set here; otherwise the cache lives in ``.jax_cache/`` at the root of the
checkout (listed in ``.gitignore``).  Entry points call
:func:`enable_compile_cache` before their first compile; importing this
module sets nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
