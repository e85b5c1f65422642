"""Placement of JAX's persistent compilation cache.

A cold run of a 32-layer step compiles for tens of seconds; the cache lets
later processes load the compiled program instead. The directory is part
of the cache's key, so it must not move between runs: it is
``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself, and nothing here overrides it), and otherwise the fixed
``.jax_cache`` directory at the root of this checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at :func:`compile_cache_dir`
    (setting nothing when the environment already names a directory) and
    return that directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
