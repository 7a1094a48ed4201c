"""Where JAX keeps its persistent compilation cache.

Entry points (``serve.py``/``train.py`` run as scripts, ``chip_smoke.py``,
``benchmarks/engine_speed.py``) call ``enable_compile_cache()`` once
before they compile anything; library modules never do. A set
``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting and wins untouched.
Otherwise the cache lives at one fixed path inside the checkout, so a
later run of the same checkout finds what an earlier one compiled. The
path is never built from a tempdir, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the cache uses: the environment's, else the checkout's."""
    return os.environ.get(ENV) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at ``compile_cache_dir()``; returns it."""
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
