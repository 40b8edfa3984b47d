"""Where entry points put JAX's persistent compilation cache."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_setting_stands(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "env"))
    assert compile_cache.enable_compile_cache() == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "env")


def test_default_is_fixed_in_checkout(monkeypatch, tmp_path,
                                      restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    want = compile_cache.CHECKOUT / ".jax_cache"
    assert (compile_cache.CHECKOUT / "src" / "repro" / "launch"
            / "compile_cache.py").is_file()
    assert compile_cache.enable_compile_cache() == str(want)
    assert jax.config.jax_compilation_cache_dir == str(want)
