"""JAX's persistent compilation cache for the repository's entry points."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# One fixed directory inside the checkout, never derived from a temporary
# name, a pid or the time, so that a later run finds what an earlier one
# stored.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and no other directory is set.  Call from an entry point's
    ``main``, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
