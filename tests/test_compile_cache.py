"""Where the entry points keep JAX's persistent compilation cache."""

from pathlib import Path

import jax

from repro.launch.compile_cache import compile_cache_dir, enable_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache_dir() == tmp_path
    assert enable_compile_cache() == tmp_path
    # JAX reads the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_one_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache_dir() == CHECKOUT / ".jax_cache"
        assert enable_compile_cache() == CHECKOUT / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(
            CHECKOUT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (CHECKOUT / ".gitignore").read_text().split()
