"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and wins:
nothing here overrides it. Otherwise the cache lives at a fixed
``<repo>/.jax_cache`` (listed in ``.gitignore``); a fixed path is part of
the cache key, so repeated runs from one checkout reuse what they compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]


def use_compile_cache() -> str:
    """Enable the persistent compile cache; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
