"""Persistent XLA compilation cache placement for the entry points.

Called from each entry point's ``main`` (never at import): the search
pipelines are bucketed per (window bucket, k, delta bucket), so a cold
process compiles many programs, and a warm cache turns each into a load.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed location inside the checkout — JAX keys cache entries by content,
#: and a stable path is what lets a later process find them again
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the directory JAX caches compiled programs in.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing is set here; otherwise the cache goes to ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
