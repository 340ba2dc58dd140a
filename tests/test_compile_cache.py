"""The persistent compilation cache helper (repro.launch.compile_cache):
an explicit JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
otherwise the cache lives at one fixed directory inside the checkout."""

import pathlib

import jax
import pytest

from repro.launch import compile_cache

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_is_honoured_and_nothing_set(monkeypatch, tmp_path,
                                             cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == cache_dir_config


def test_fixed_in_repo_path_otherwise(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == str(REPO_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable() == path          # same path every call
