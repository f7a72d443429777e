"""Where the persistent compilation cache goes (``launch.compile_cache``)."""
import pathlib
import subprocess

import jax
import pytest

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_placed_directory_wins_and_nothing_is_set(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_checkout_cache(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # a second call (another entry point in the same process) is the same
    assert compile_cache.enable() == path


def test_default_cache_is_git_ignored():
    out = subprocess.run(["git", "check-ignore", "-q", ".jax_cache/x"],
                         cwd=ROOT, capture_output=True)
    if out.returncode == 128:
        pytest.skip("not a git checkout")
    assert out.returncode == 0
