"""Persistent XLA compilation cache for the entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself: when it is set, the cache
lives there and this module changes nothing. Otherwise the cache goes to
``<repo root>/.jax_cache``, a fixed path, so every later run from the
same checkout finds what an earlier one compiled. Entry points call
``enable_compile_cache()`` once at start-up; importing this module has
no effect.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
