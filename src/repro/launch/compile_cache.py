"""Persistent XLA compilation cache for the entry points.

A cache keyed in part by its own path only hits when the path is fixed, so
it lives at one place per checkout: ``JAX_COMPILATION_CACHE_DIR`` when that
is set (JAX reads it itself), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir, os.pardir, os.pardir))


def enable_compile_cache() -> str:
    """Turns the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
