"""JAX's persistent compile cache, kept in one fixed place.

A cold compile of a full-width train step costs minutes, so every entry
point that compiles for the chip (``launch/train.py``, ``chip_smoke.py``,
the ``launch/multiproc`` workers) calls :func:`enable_compile_cache` before
its first compile.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it. Otherwise the cache lives at ``<repo>/.jax_cache``
(gitignored): a fixed path, never a temp, pid or time-stamped one, since a
directory that moves is never found again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one directory and return
    that directory."""
    env = os.environ.get(ENV_VAR, "").strip()
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
