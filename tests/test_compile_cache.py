"""Where the persistent compile cache lands (``repro.launch.cache``)."""
import os

import jax
import pytest

from repro.launch import cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env", [None, "/somewhere/else"])
def test_cache_dir_follows_env_or_checkout(monkeypatch, restore_cache_dir,
                                           env):
    jax.config.update("jax_compilation_cache_dir", None)
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    got = cache.enable_compile_cache()
    if env is None:
        # a fixed path inside the checkout, the same on every run
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    else:
        # JAX reads the variable itself: the helper sets nothing
        assert got == env
        assert jax.config.jax_compilation_cache_dir is None
