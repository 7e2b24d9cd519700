"""Where the entry points put JAX's persistent compilation cache."""

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_unset_env_uses_the_fixed_in_checkout_dir(monkeypatch, restore_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    where = compile_cache.use_compile_cache()
    assert where == str(compile_cache.CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == where
    # fixed: inside the checkout, never a temporary or per-run name
    assert compile_cache.CACHE_DIR.parent == compile_cache.Path(
        compile_cache.__file__
    ).resolve().parents[2]


def test_env_dir_wins_and_nothing_else_is_set(monkeypatch, restore_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
