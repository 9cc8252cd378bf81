"""Where the persistent compilation cache goes (repro.compile_cache).

Each test restores JAX's cache setting, so the suite itself never runs
with a persistent cache."""

from pathlib import Path

import jax
import pytest

from repro.compile_cache import DEFAULT_DIR, ENV_VAR, enable_compile_cache


@pytest.fixture
def cache_setting():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_is_left_to_jax(monkeypatch, tmp_path, cache_setting):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it and no code sets
    another directory."""
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_path_in_the_checkout(monkeypatch, cache_setting):
    monkeypatch.delenv(ENV_VAR, raising=False)
    repo = Path(__file__).resolve().parents[1]
    assert enable_compile_cache() == DEFAULT_DIR == repo / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
