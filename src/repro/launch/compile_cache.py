"""Persistent XLA compilation cache for the repo's entry points.

A cold process compiles every round, probe and eval program from
scratch; JAX's persistent cache lets the next process load them.  The
cache key includes the directory, so the directory must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX
reads it itself), otherwise the cache lives at a fixed, git-ignored
``.jax_cache`` beside ``src/``.

Entry points (``chip_smoke.py``, the examples, ``benchmarks/run.py``)
call :func:`enable_compile_cache` once, before their first compile; the
library and the tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Leaves JAX's own choice alone when
    ``JAX_COMPILATION_CACHE_DIR`` is set."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
