"""Where ``launch.cache.enable_persistent_cache`` puts JAX's cache."""
from pathlib import Path

import jax
import pytest

from repro.launch import cache

REPO = Path(__file__).resolve().parents[1]
FLAGS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def restore_config():
    from jax.experimental.compilation_cache import compilation_cache
    saved = {f: getattr(jax.config, f) for f in FLAGS}
    yield
    for f, v in saved.items():
        jax.config.update(f, v)
    compilation_cache.reset_cache()


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    # JAX reads JAX_COMPILATION_CACHE_DIR into its config at start-up;
    # the program must not set any other directory over it
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert cache.enable_persistent_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_dir_is_one_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    first = cache.enable_persistent_cache()
    second = cache.enable_persistent_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_opt_out_sets_nothing(monkeypatch):
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_persistent_cache(enabled=False) is None
    assert jax.config.jax_compilation_cache_dir == before
