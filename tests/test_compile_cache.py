"""Where the persistent compilation cache goes (repro.compile_cache)."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.compile_cache import REPO_CACHE_DIR, enable_compile_cache


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _listing(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


def test_env_dir_is_used_and_nothing_else(tmp_path, monkeypatch,
                                          restore_cache_config):
    cache = tmp_path / "cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    in_tree = _listing(REPO_CACHE_DIR)
    compilation_cache.reset_cache()
    assert enable_compile_cache() == str(cache)
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 0.125)(
        jnp.arange(7, dtype=jnp.float32)).block_until_ready()
    assert _listing(cache), "no cache entry written to the env directory"
    assert _listing(REPO_CACHE_DIR) == in_tree


def test_unset_env_uses_fixed_in_tree_dir(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(REPO_CACHE_DIR) == jax.config.jax_compilation_cache_dir
    assert REPO_CACHE_DIR.name == ".jax_cache"
    assert (REPO_CACHE_DIR.parent / "src" / "repro").is_dir()
