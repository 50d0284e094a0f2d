"""The persistent compilation cache sits at one fixed place: where
``JAX_COMPILATION_CACHE_DIR`` says, else ``.jax_cache/`` in the checkout."""

from __future__ import annotations

from pathlib import Path

import jax
import pytest

from page_rank_and_tfidf_using_apache_spark_tpu.utils.compile_cache import (
    enable_compile_cache,
)

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def jax_cache_dir():
    """Restore JAX's cache setting after the test (no compile runs in
    between, so the cache itself is never initialized here)."""
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, jax_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == jax_cache_dir


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch, jax_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert enable_compile_cache() == first
    assert Path(first) == REPO / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == first
