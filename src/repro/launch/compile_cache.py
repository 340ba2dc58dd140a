"""Where JAX keeps its persistent compilation cache.

The path is part of a cache entry's key, so it must not move between
runs: when JAX_COMPILATION_CACHE_DIR is set, JAX reads that directory
itself and nothing is set here; otherwise the cache lives at a fixed
directory inside the checkout (listed in .gitignore).
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache for this process and
    return its directory. Call before the first compile."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
