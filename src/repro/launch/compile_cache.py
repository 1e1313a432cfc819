"""Persistent XLA compilation cache for the entry points.

Called by ``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py`` and
the ``benchmarks`` package — never at import of ``repro``, so a library
user or a test keeps whatever cache setting it already has.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here.  Otherwise the cache lives at one fixed path inside
the checkout, ``.jax_cache/`` (git-ignored): the path is part of what a
later run must find again, so it is never made from a temporary name, a
process id or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
