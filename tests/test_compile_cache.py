"""The persistent compile cache lives in one place.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own and the helper sets
nothing; otherwise the cache is the fixed, gitignored ``<repo>/.jax_cache``.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_default_dir_is_fixed_inside_the_repo():
    assert compile_cache.DEFAULT_DIR == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_points_jax_at_the_repo_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_entries_land_in_the_env_dir(tmp_path):
    """A compile in a fresh process writes its entry where the variable
    points."""
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "jax.config.update("
            "'jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()"
            "\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert any(tmp_path.iterdir())


def test_cache_key_includes_metadata(monkeypatch, tmp_path):
    """Programs that differ only in their named scopes get separate
    entries, so a cached executable never carries another program's
    operation names into a device trace."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          before)
