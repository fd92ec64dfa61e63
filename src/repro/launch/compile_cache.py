"""Persistent XLA compilation cache for the chip entry points.

Call ``enable_compile_cache()`` first thing in a program that runs on the
chip (``chip_smoke.py``, ``launch/serve.py``); library code and tests never
call it. ``JAX_COMPILATION_CACHE_DIR``, when set, wins and JAX reads it
itself; otherwise the cache lives at the fixed ``<repo>/.jax_cache`` (a
fixed path, because the path is part of the cache key).
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn the cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
