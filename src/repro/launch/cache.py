"""Persistent XLA compilation cache at a path that can be placed from outside.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
nothing here overrides it.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache``.  The path is part of what a later run has to
find again, so it is never built from a temporary name, a pid or the time.
Every executable is kept, however quick its compile: under JAX's default
threshold (1 s of compile time) a compile near it is kept by some runs
and not by others, so what a later run compiles again, and the host
memory that compile leaves behind, would change from run to run.
Call ``enable_compile_cache()`` before the first compile.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory and keep every
    compile there; returns that path."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
