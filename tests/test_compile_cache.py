"""Where ``configure_compile_cache`` puts JAX's persistent compile cache.

JAX decides once per process whether the cache is used, so each test
resets that decision before and after, and restores the settings it
changed: no other test sees a cache.
"""
import itertools

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import compile_cache

_SETTINGS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs")
_fresh = itertools.count(1)


@pytest.fixture
def dirs(tmp_path, monkeypatch):
    """(the directory the environment names, the checkout default), with
    JAX's cache settings restored afterwards."""
    was = {k: getattr(jax.config, k) for k in _SETTINGS}
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", default_dir)
    compilation_cache.reset_cache()
    yield env_dir, default_dir
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _compile_something():
    """Compile a program no earlier call has compiled."""
    c = next(_fresh)
    jax.jit(lambda x: x * c + 1).lower(jnp.zeros((8,), jnp.int32)).compile()


def _written(d):
    return sorted(p.name for p in d.iterdir()) if d.exists() else []


def test_cache_defaults_to_the_checkout(dirs):
    env_dir, default_dir = dirs
    assert compile_cache.configure_compile_cache() == str(default_dir)
    _compile_something()
    assert _written(default_dir)
    assert not _written(env_dir)


def test_environment_directory_stands(dirs, monkeypatch):
    env_dir, default_dir = dirs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(env_dir))
    # what JAX does with the variable when it is imported
    jax.config.update("jax_compilation_cache_dir", str(env_dir))
    assert compile_cache.configure_compile_cache() == str(env_dir)
    assert jax.config.jax_compilation_cache_dir == str(env_dir)
    _compile_something()
    assert _written(env_dir)
    assert not _written(default_dir)


@pytest.mark.parametrize("env_set", [False, True])
def test_multi_device_process_keeps_no_cache(dirs, monkeypatch, env_set):
    env_dir, default_dir = dirs
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(env_dir))
        jax.config.update("jax_compilation_cache_dir", str(env_dir))
    assert compile_cache.configure_compile_cache(multi_device=True) is None
    _compile_something()
    assert not _written(env_dir)
    assert not _written(default_dir)
