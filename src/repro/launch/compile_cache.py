"""Persistent compilation cache placement for the launchers and the chip
smoke script.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set, that is
the cache and nothing here overrides it. Otherwise the cache lives at one
fixed path inside the checkout (``<repo>/.jax_cache``, git-ignored): the
path is part of the cache key, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
