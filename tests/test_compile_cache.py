"""The persistent compilation cache's place: ``$JAX_COMPILATION_CACHE_DIR``
when set, else a fixed directory in the caller's checkout."""
from __future__ import annotations

from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

from repro.launch import compile_cache  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent


def test_env_var_wins_and_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.compile_cache_dir(CHECKOUT) == tmp_path
    assert compile_cache.use_compile_cache(CHECKOUT) == tmp_path
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_checkout_cache(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = CHECKOUT / ".jax_cache"
    assert compile_cache.compile_cache_dir(CHECKOUT) == want
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.use_compile_cache(CHECKOUT) == want
        assert jax.config.jax_compilation_cache_dir == str(want)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
