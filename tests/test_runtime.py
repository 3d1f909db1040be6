"""The persistent compile cache: placed from outside through
``JAX_COMPILATION_CACHE_DIR``, otherwise at one fixed path in the checkout."""
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import runtime

REPO_ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def test_env_var_places_the_cache(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_cache_is_fixed_in_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = runtime.enable_compile_cache()
    assert runtime.enable_compile_cache() == first
    assert Path(first) == REPO_ROOT / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == first
    assert ".jax_cache/" in (REPO_ROOT / ".gitignore").read_text().split()
