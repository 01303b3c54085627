"""Persistent XLA compilation cache for the entry points.

A run on a fresh machine compiles every program it dispatches; with the
persistent cache a second run of the same programs reads them back. A
run finds only what an earlier run wrote to the same directory, so the
directory must not move between runs: it is ``$JAX_COMPILATION_CACHE_DIR`` when that is set
(JAX reads the variable itself and nothing is set here), and otherwise
the fixed ``<repo>/.jax_cache`` (listed in ``.gitignore``).

Only entry points call :func:`enable_compile_cache` (``chip_smoke.py``,
``repro.launch.train.main``, ``benchmarks.run.main``); importing this
module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
