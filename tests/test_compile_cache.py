"""The persistent compilation cache lives in exactly one directory."""
from __future__ import annotations

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_names_the_cache_dir(monkeypatch, tmp_path,
                                     restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_cache_dir_is_fixed_at_the_checkout_root(monkeypatch,
                                                         restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert (compile_cache.DEFAULT_DIR.parent / "chip_smoke.py").is_file()
    assert compile_cache.enable_compile_cache() == path
