"""JAX persistent compilation cache, placed from outside the program.

A cold start compiles every unrolled serving program; the persistent
cache lets the next process skip that. Its location is a deployment
setting: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at
start-up and this module sets nothing. Otherwise the cache lives at one
fixed path inside the checkout, ``<repo>/.jax_cache`` — fixed because a
cache entry is only found again under the same directory.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Entry points call this once, before their first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
