"""Persistent XLA compilation cache for the launchers and ``bench/``.

Compiling the serving steps at published widths takes most of a cold
run, and every process (and every call on a fresh chip host) starts
with no compiled code.  The cache keeps compiled programs on disk:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
  is configured here.
* otherwise: a fixed ``.jax_cache/`` at the repository root (listed in
  ``.gitignore``).  The path is part of what makes a hit possible, so it
  never depends on a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
