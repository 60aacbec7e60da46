"""The compile-cache helper: JAX_COMPILATION_CACHE_DIR wins untouched,
otherwise one fixed directory inside the checkout."""
from pathlib import Path

import jax
import pytest

from repro.compile_cache import CHECKOUT, use_compile_cache


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_is_left_to_jax(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_uses_fixed_checkout_path(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CHECKOUT == Path(__file__).resolve().parents[1]
    want = str(CHECKOUT / ".jax_cache")
    assert use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same path on every call: a moving directory never hits
    assert use_compile_cache() == want
