"""The persistent compilation cache sits at a fixed place: the
``JAX_COMPILATION_CACHE_DIR`` directory when set, else
``<checkout>/.jax_cache``."""
from __future__ import annotations

from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import place_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_wins_and_nothing_is_set(monkeypatch, restore_cache_dir,
                                         tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert place_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_under_the_checkout(monkeypatch,
                                                 restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = place_compile_cache()
    assert first == place_compile_cache() == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert ".jax_cache/" in (CHECKOUT / ".gitignore").read_text().split()
