"""The persistent compilation cache's directory (repro.launch.compile_cache):
an exported JAX_COMPILATION_CACHE_DIR is used as is, and otherwise the
cache sits at one fixed path inside the checkout."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_exported_dir_wins_and_code_sets_none(monkeypatch, cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.use_compile_cache()
    assert first == compile_cache.use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == first
    root = compile_cache.REPO_CACHE_DIR.parent
    assert (root / "chip_smoke.py").is_file() and (root / "src").is_dir()
    assert first == str(root / ".jax_compile_cache")
