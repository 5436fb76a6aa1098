"""Where the entry points keep JAX's persistent compilation cache."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_dir_config():
    """Restores ``jax_compilation_cache_dir`` so no later test in this
    process writes a persistent cache."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_fixed_in_checkout_path_when_env_unset(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable()
    assert got == str(compile_cache.CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.CACHE_DIR.parent.joinpath("pyproject.toml").exists()
    assert compile_cache.enable() == got        # the same place every run


def test_env_var_wins_and_nothing_is_set(monkeypatch, cache_dir_config,
                                         tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
