"""The persistent-compile-cache helper the entry points call first
(`repro.compile_cache`): the environment's directory wins, otherwise a
fixed directory inside the checkout."""
from pathlib import Path

import jax

from repro import compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


def test_env_var_is_honoured(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == tmp_path
    # JAX reads the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir()
    assert first == CHECKOUT / ".jax_cache"
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == str(first)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
