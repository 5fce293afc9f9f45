"""JAX's persistent compilation cache for the entry points.

``JAX_COMPILATION_CACHE_DIR``, where set, names the directory.  Otherwise
the cache lives at ``<checkout>/.jax_cache``: a fixed path, because the
path is part of what a later run must find again.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    return os.environ.get(ENV) or str(DEFAULT_DIR)


def enable_compilation_cache() -> str:
    """Point JAX's persistent cache at :func:`cache_dir`; returns it."""
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
