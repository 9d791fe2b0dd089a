"""The persistent compilation cache is placed from outside, never by import."""

import jax

from repro.launch import compile_cache as CC


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(CC.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert CC.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert CC.use_compile_cache() == str(CC.CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(CC.CHECKOUT_CACHE)
        assert CC.CHECKOUT_CACHE.parent.joinpath("src", "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
