"""The persistent compilation cache lives at one fixed path."""

import os

import jax

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cache_defaults_to_checkout_dir(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert got == os.path.join(REPO, ".jax_cache")


def test_cache_env_var_stands(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own reading of it is kept."""
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    # what JAX does with the variable when it is set before import
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        got = compile_cache.enable_compile_cache()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert got == str(tmp_path)
