"""Persistent XLA compilation cache for the command-line entry points.

Compiling the 12-layer step programs dominates a cold start on the chip, so
every entry point (``repro.launch.serve``, ``repro.launch.train``,
``chip_smoke.py``) calls :func:`enable_compile_cache` before it compiles
anything. Importing the library sets nothing.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets no other directory. Otherwise the cache lives at one fixed path inside
the checkout (``<repo>/.jax_cache``, git-ignored): the path is part of the
cache key, so a directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
