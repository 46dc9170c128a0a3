"""The launchers' compile-cache placement: the environment's directory when
`JAX_COMPILATION_CACHE_DIR` names one, else `<checkout>/.jax_cache`."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO_ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_set_leaves_config_alone(monkeypatch, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/jax_cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("env_value", [None, ""])
def test_env_dir_unset_uses_checkout_dir(monkeypatch, restore_cache_dir,
                                         env_value):
    if env_value is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, env_value)
    got = compile_cache.enable_compile_cache()
    assert got == REPO_ROOT / ".jax_cache" == compile_cache.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == str(got)


def test_checkout_dir_is_git_ignored():
    ignored = (REPO_ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
