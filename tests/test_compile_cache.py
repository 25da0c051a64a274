"""repro.compile_cache: where the persistent compilation cache goes."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_is_left_to_jax(monkeypatch, restore_cache_dir, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, enable() reports it and sets
    no directory of its own."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_uses_fixed_checkout_dir(monkeypatch, restore_cache_dir):
    """Unset, the cache goes to one fixed directory inside the checkout:
    the same path on every call, so a later run finds earlier entries."""
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable()
    assert first == compile_cache.enable()
    assert first == str(compile_cache.DEFAULT_DIR)
    assert compile_cache.DEFAULT_DIR.parent.joinpath("pyproject.toml").exists()
    assert jax.config.jax_compilation_cache_dir == first
