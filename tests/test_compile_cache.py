"""The persistent compilation cache lives where JAX_COMPILATION_CACHE_DIR
says, else at one fixed directory inside the checkout."""
from pathlib import Path

import jax

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_env_var_wins(tmp_path):
    env = {compile_cache.ENV_VAR: str(tmp_path)}
    assert compile_cache.cache_dir(env) == str(tmp_path)


def test_default_is_the_checkout_dir():
    assert compile_cache.cache_dir({}) == str(REPO / ".jax_cache")
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_enable_leaves_the_env_var_in_charge(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_enable_without_env_var_uses_the_checkout_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        assert compile_cache.enable() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
