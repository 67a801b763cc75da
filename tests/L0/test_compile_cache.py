"""The compile cache is placed from outside, or at one fixed path."""

import os

import jax
import pytest

from apex_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture
def cache_config():
    """Restore the process-wide setting: the rest of the session must
    not start writing a cache because this file ran."""
    before = jax.config.jax_compilation_cache_dir
    frames = jax.config.jax_traceback_in_locations_limit
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_traceback_in_locations_limit", frames)


def test_placed_from_outside_sets_nothing_in_code(monkeypatch, cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/x")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() == "/x"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_checkout_from_any_cwd(monkeypatch, tmp_path,
                                              cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    for name in ("a", "b"):
        cwd = tmp_path / name
        cwd.mkdir()
        monkeypatch.chdir(cwd)
        assert compile_cache.configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want


def test_a_location_keeps_two_frames(monkeypatch, cache_config):
    """A kernel's serialized body carries its locations: with the
    caller of the jitted function in them an executable's cache key
    would move with that caller, and a second lowering from elsewhere
    would miss."""
    def lowered_from(depth):
        if depth:
            return lowered_from(depth - 1)
        return jax.jit(lambda x: x * 2).lower(1.0).as_text(debug_info=True)

    monkeypatch.setenv(compile_cache.ENV_VAR, "/x")
    assert jax.config.jax_traceback_in_locations_limit > 2
    assert lowered_from(0) != lowered_from(3)
    compile_cache.configure_compile_cache()
    assert jax.config.jax_traceback_in_locations_limit == 2
    assert lowered_from(0) == lowered_from(3)


def test_cache_dir_is_ignored_by_git():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
