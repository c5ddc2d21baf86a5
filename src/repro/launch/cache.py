"""Persistent JAX compilation cache, placed from outside.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
helper sets nothing.  Otherwise the cache lives at one fixed path inside
the checkout, ``<repo>/.jax_cache`` (git-ignored).  The directory is part
of what a cached entry is found by, so it is never made from a temp name,
a pid or the time.  Entry points call ``use_compile_cache`` at start-up;
importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
