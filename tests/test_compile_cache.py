"""The persistent compile cache sits where JAX_COMPILATION_CACHE_DIR says,
or else at one fixed path inside the checkout."""
from pathlib import Path

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_dir_setting():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_environment_setting_stands(monkeypatch, cache_dir_setting):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/jax-cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_and_inside_the_checkout(monkeypatch,
                                                  cache_dir_setting):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.configure()
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.configure() == path
    root = Path(__file__).resolve().parents[1]
    assert Path(path) == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
