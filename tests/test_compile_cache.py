"""The persistent-compilation-cache placement helper (CPU only)."""

from pathlib import Path

import jax
import pytest

from repro.compile_cache import (CACHE_ENV, DEFAULT_CACHE_DIR,
                                 enable_compile_cache)

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_owns_the_location(monkeypatch, tmp_path,
                                   restore_cache_dir):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own setting stands: the
    helper reports it and configures no directory of its own."""
    monkeypatch.setenv(CACHE_ENV, str(tmp_path / "cache"))
    cache = enable_compile_cache()
    try:
        assert cache.directory == str(tmp_path / "cache")
        assert jax.config.jax_compilation_cache_dir == restore_cache_dir
    finally:
        cache.close()


def test_default_is_one_fixed_ignored_path_in_the_checkout(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    first = enable_compile_cache()
    second = enable_compile_cache()
    try:
        assert first.directory == second.directory == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == first.directory
        assert DEFAULT_CACHE_DIR.parent == REPO
        ignored = (REPO / ".gitignore").read_text().split()
        assert f"{DEFAULT_CACHE_DIR.name}/" in ignored
    finally:
        first.close()
        second.close()


def test_counts_hits_and_writes_until_closed(monkeypatch, tmp_path,
                                             restore_cache_dir):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    cache = enable_compile_cache()
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    cache.close()
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert (cache.hits, cache.writes) == (2, 1)
    assert "hits=2 writes=1" in cache.summary()
