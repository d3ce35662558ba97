"""Where the persistent compilation cache goes (``launch.compile_cache``).

Only the pure path rule is exercised: the test suite never turns the
cache on."""

import pathlib

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_cache_dir_is_the_env_var_when_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)


def test_cache_dir_is_the_fixed_repo_path_otherwise(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.compile_cache_dir() == str(ROOT / ".jax_cache")
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
