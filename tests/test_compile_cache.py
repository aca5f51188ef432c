"""Where the persistent compilation cache goes (repro/compile_cache.py)."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture()
def restore_cache_dir():
    saved = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


def test_env_var_directory_is_left_to_jax(monkeypatch, tmp_path,
                                          restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_directory_is_in_the_checkout(monkeypatch,
                                              restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    assert (compile_cache.DEFAULT_DIR.parent / "pyproject.toml").exists()
    assert jax.config.jax_compilation_cache_dir == path
