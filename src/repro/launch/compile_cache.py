"""The one place that turns on JAX's persistent compilation cache.

Entry points that compile for a chip (`chip_smoke.py`, `python -m
repro.launch.serve`, `benchmarks/run.py`) call `enable()` before their
first compile.  With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it
itself and nothing is set here.  Otherwise the cache goes to a fixed
directory inside the checkout (``.jax_cache``, git-ignored): the path is
part of the cache key, so it must not move between runs.  Tests never
call this.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (this file is ``src/repro/launch/...``)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
