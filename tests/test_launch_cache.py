"""repro.launch.cache: the compile cache goes where the environment says,
else to one fixed directory in the repository."""
from pathlib import Path

import jax
import pytest

from repro.launch import cache


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_nothing_is_set(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_the_fixed_repo_dir(monkeypatch, cache_config):
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    path = cache.enable_compile_cache()
    repo = Path(__file__).resolve().parents[1]
    assert path == str(repo / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert cache.enable_compile_cache() == path      # stable across calls
