"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``python -m repro.launch.serve``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` before its
first compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, names the
directory and nothing else is set.  Otherwise the cache lives at the
fixed path ``<checkout>/.jax_cache`` (git-ignored): the path is part of
the cache key, so it never carries a temp name, a pid or a time.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
