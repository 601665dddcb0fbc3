"""Where the entry points put JAX's persistent compilation cache."""
from __future__ import annotations

import jax
import pytest

from repro.launch import compile_cache as CC


@pytest.fixture
def cache_dir_config():
    """Restore the cache directory setting after the test, so no later
    test in this process compiles into a persistent cache."""
    before = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_set_directory_wins_on_every_backend(cache_dir_config, monkeypatch,
                                             tmp_path):
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert CC.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_unset_on_accelerator_uses_fixed_checkout_directory(
        cache_dir_config, monkeypatch):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    path = CC.use_compile_cache()
    assert path == str(CC.CHECKOUT_CACHE)
    assert jax.config.jax_compilation_cache_dir == path
    assert CC.CHECKOUT_CACHE.name == ".jax_cache"
    assert (CC.CHECKOUT_CACHE.parent / "pyproject.toml").is_file()
    assert CC.use_compile_cache() == path          # the same on every call


def test_unset_on_cpu_sets_no_cache(cache_dir_config, monkeypatch):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert CC.use_compile_cache() is None
    assert not jax.config.jax_compilation_cache_dir
