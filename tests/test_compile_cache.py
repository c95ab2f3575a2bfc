"""``repro.compile_cache``: the environment's cache directory wins; without
it the cache sits at the fixed, git-ignored ``<repo>/.jax_cache``."""

from __future__ import annotations

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env_dir", ["/elsewhere/jax-cache", None],
                         ids=["env", "unset"])
def test_enable_compile_cache(monkeypatch, cache_config, env_dir):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    got = compile_cache.enable_compile_cache()
    if env_dir is None:
        assert got == str(compile_cache.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
    else:
        assert got == env_dir
        assert jax.config.jax_compilation_cache_dir == before  # left to JAX


def test_cache_dir_is_fixed_and_ignored():
    root = compile_cache.REPO_ROOT
    assert compile_cache.CACHE_DIR == root / ".jax_cache"
    assert (root / "src" / "repro").is_dir()
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
