"""Where JAX keeps its persistent compilation cache.

The entry points (``launch/serve.py``, ``benchmarks/run.py``,
``chip_smoke.py``) call ``enable_compile_cache()`` once at start-up;
nothing turns the cache on at import or in tests. A set
``JAX_COMPILATION_CACHE_DIR`` wins. Otherwise the cache lives at one
fixed directory inside the checkout (``.jax_cache``, git-ignored): the
path is part of what a later run must find again, so it never depends
on a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def compile_cache_dir(environ: Optional[Mapping[str, str]] = None) -> str:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<repo>/.jax_cache``."""
    env = os.environ if environ is None else environ
    return env.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return the directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
