"""Persistent JAX compilation cache for the entry points.

Each launcher calls :func:`enable` at the start of its ``main()``, never
at import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here overrides it. Otherwise the cache goes to one
fixed directory inside the checkout (``.jax_cache/``, git-ignored), so
every run of the same checkout finds what earlier runs compiled.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
