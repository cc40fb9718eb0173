"""The one placement rule for the persistent XLA compile cache
(dml_tpu/compile_cache.py): placed from outside, or a fixed path inside
the checkout — never a directory code makes up per run."""

import os

import jax
import pytest

from dml_tpu import compile_cache as cc


@pytest.fixture
def cache_config():
    """Run with the session's cache config put back afterwards."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_set_means_code_sets_nothing(monkeypatch, cache_config, tmp_path):
    placed = str(tmp_path / "placed_from_outside")
    monkeypatch.setenv(cc.CACHE_ENV, placed)
    jax.config.update("jax_compilation_cache_dir", "untouched-sentinel")
    assert cc.configure_compile_cache() == placed
    # JAX read the variable at import; the helper must not overwrite
    # whatever the config holds
    assert jax.config.jax_compilation_cache_dir == "untouched-sentinel"


def test_env_unset_means_fixed_path_in_checkout(monkeypatch, cache_config):
    monkeypatch.delenv(cc.CACHE_ENV, raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    got = cc.configure_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(repo, ".jax_cache") == cc.DEFAULT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == got
    # listed in .gitignore, so a run leaves nothing git would commit
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    # the same answer every time: the path is part of every cache key
    assert cc.configure_compile_cache() == got
