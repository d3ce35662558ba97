"""Where JAX keeps its persistent compilation cache.

Entry points (``launch.train``, ``launch.serve``, ``examples/train_e2e``,
``benchmarks.run``, ``chip_smoke.py``) call :func:`enable_compile_cache`
once, before their first compile; importing a module never does, and
the test suite never does.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing else
  is set here.
* otherwise — the fixed ``.jax_cache/`` at the repo root (git-ignored).
  The path is part of every cache key, so it never holds a temporary
  name, a pid or a time.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The cache directory the rule above picks (no side effects)."""
    return os.environ.get(ENV_VAR) or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
