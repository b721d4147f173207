"""The entry points' persistent compilation cache location
(``launch/compile_cache.py``): ``JAX_COMPILATION_CACHE_DIR`` wins and
nothing is set in code; otherwise the fixed ``<repo>/.jax_cache``."""
import pathlib

import jax
import pytest

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_wins_and_nothing_is_set(monkeypatch, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/jax-cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_repo_cache_is_the_fixed_default(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.use_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.use_compile_cache() == path  # stable across calls
