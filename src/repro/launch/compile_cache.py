"""JAX's persistent compilation cache for the entry points.

An entry point (``launch.serve``, ``benchmarks.run``, ``chip_smoke.py``)
calls ``enable_compile_cache()`` once at start; nothing enables it at
import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
no other directory is set here. Otherwise the cache is the fixed
``.jax_cache/`` at the repository root, never a temporary or per-run path:
a directory that changed between runs would never be found again.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where compiled programs are cached: the environment's choice, else
    the repository's fixed ``.jax_cache/``."""
    return os.environ.get(ENV_VAR) or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on for this process; returns its path. Not
    on the CPU backend (returns None): XLA:CPU's cached executables warn on
    every load about target features, and its programs compile in seconds."""
    if jax.default_backend() == "cpu":
        return None
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
