"""`launch.compile_cache`: the persistent compilation cache sits where the
environment puts it, else at one fixed path inside the checkout."""

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache as CC


@pytest.fixture()
def cache_dir_config():
    """Restore JAX's cache-dir option, and the cache built from it, after
    the test."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_env_placed_cache_is_left_alone(cache_dir_config, monkeypatch,
                                        tmp_path):
    monkeypatch.setenv(CC.ENV_VAR, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert CC.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_default_cache_is_fixed_in_checkout(cache_dir_config, monkeypatch):
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    got = CC.configure_compile_cache()
    root = Path(__file__).resolve().parents[1]
    assert got == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert CC.configure_compile_cache() == got   # stable across calls
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
