"""The entry points' compile-cache helper: JAX's own directory when
`JAX_COMPILATION_CACHE_DIR` is set, else one fixed, gitignored directory
inside the checkout."""
import pathlib

import jax
import pytest

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_env_dir_wins_and_no_other_is_set(monkeypatch, tmp_path,
                                          restore_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_and_ignored(monkeypatch, restore_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.enable() == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
