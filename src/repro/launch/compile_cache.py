"""JAX's persistent compilation cache for the repo's entry points.

`JAX_COMPILATION_CACHE_DIR`, when set, is honoured as it is (JAX reads it
itself, and nothing here names another directory). Otherwise the cache
lives at one fixed path inside the checkout, `<repo>/.jax_cache` (listed in
`.gitignore`): the path is part of what a later process must find, so it
never carries a temp name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
