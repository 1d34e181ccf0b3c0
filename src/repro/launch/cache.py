"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``python -m repro.launch.serve``) call ``enable_compile_cache`` before
their first compile, so a second run of the same programs loads them
instead of compiling again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (this file is ``src/repro/launch/cache.py``).
#: A fixed path: a cache directory that moves between runs is never hit.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already taken
    the directory from it and nothing is changed here. Otherwise the
    cache goes to ``DEFAULT_CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
