"""JAX's persistent compile cache, kept in one fixed place.

A cold compile of a full-width train step costs minutes, so every entry
point that compiles for the chip (``launch/train.py``, ``chip_smoke.py``,
the ``launch/multiproc`` workers) calls :func:`enable_compile_cache` before
its first compile.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it. Otherwise the cache lives at ``<repo>/.jax_cache``
(gitignored): a fixed path, never a temp, pid or time-stamped one, since a
directory that moves is never found again.

Either way the cache key includes the program's metadata. JAX leaves it out
by default, so two programs that differ only in their ``jax.named_scope``
names (``repro.obs.scopes``) would share an entry, and a device trace of
the second would carry the first one's operation names.
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
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get(ENV_VAR, "").strip()
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
