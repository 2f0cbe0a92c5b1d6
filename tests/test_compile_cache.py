"""The persistent compilation cache the entry points turn on."""

from __future__ import annotations

import pathlib

import jax
import pytest

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_is_left_alone(monkeypatch, tmp_path, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_dir_in_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert compile_cache.enable_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
    assert pathlib.Path(first).parent == REPO
    assert f"{pathlib.Path(first).name}/" in (REPO / ".gitignore").read_text().split()

