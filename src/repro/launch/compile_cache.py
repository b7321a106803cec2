"""Persistent XLA compilation cache for the command-line entry points.

Called by the programs a user runs (`chip_smoke.py`, `repro.launch.train`,
`benchmarks.run`), never by tests or library code.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed path: the cache directory is part of the cache key, so it must
# not move between runs
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the cache dir.

    A set ``JAX_COMPILATION_CACHE_DIR`` is left to JAX (which reads it
    itself); otherwise the cache goes to ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
