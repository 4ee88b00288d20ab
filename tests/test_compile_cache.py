"""Where the entry points keep XLA's persistent compilation cache."""

from __future__ import annotations

from pathlib import Path

import jax

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache


def _with_cache_dir(fn):
    was = jax.config.jax_compilation_cache_dir
    try:
        return fn()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def run():
        before = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    _with_cache_dir(run)


def test_default_is_fixed_repo_directory(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def run():
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)

    _with_cache_dir(run)
    repo = Path(__file__).resolve().parents[1]
    assert REPO_CACHE_DIR == repo / ".jax_cache"
