"""JAX's persistent compilation cache for the command-line entry points.

Called by the mains (`chip_smoke.py`, `launch/summary_serve.py`,
`launch/chaos.py`, `benchmarks/run.py`), never at import: library users and
the tests keep JAX's own default (no persistent cache).

Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing
is set here. Otherwise the cache lives at the fixed path ``<repo>/.jax_cache``
— the directory is part of the cache key, so a path that moved between runs
(a temp dir, a pid, a timestamp) would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
