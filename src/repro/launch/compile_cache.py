"""Where JAX keeps its persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself: when it is set, that
directory is the cache and nothing here overrides it. Otherwise the cache
lives at ``<checkout>/artifacts/jax_cache``, an absolute path derived from
this file's location, so every entry point shares one cache whatever its
working directory.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[3]
                  / "artifacts" / "jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
