"""Persistent XLA compilation cache at a placeable, fixed path.

The cache key includes the directory, so the directory must not move
between runs: a temporary or per-process path never hits. Rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  here, so whoever launches the program decides where the cache lives.
- otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

Call ``enable_compile_cache()`` before the first compile.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
