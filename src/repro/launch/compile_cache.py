"""Persistent XLA compilation cache for the entry points.

Warmup compiles every (prefill chunk × KV span) program plus decode, so a
cold process pays for all of them.  The entry points (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``) call ``enable()`` first: the
cache lives where ``JAX_COMPILATION_CACHE_DIR`` says when it is set, and
otherwise at the fixed ``<repo>/.jax_cache`` (the directory is part of the
cache key, so it must not move between runs).  Tests never enable it.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def cache_dir() -> str:
    """The directory ``enable()`` uses: the environment's, else the repo's."""
    return os.environ.get(ENV_VAR) or str(REPO_ROOT / ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
