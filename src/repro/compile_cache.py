"""Persistent XLA compile cache for the program's entry points.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set: JAX reads it itself and
nothing here overrides it.  Otherwise the cache lives at a fixed path in the
checkout, ``<repo>/.jax_cache`` (git-ignored) — fixed because the path is
part of the cache's key, so a directory that moves between runs never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
