"""Where the persistent XLA compilation cache lives.

One rule for every entry point (the three drivers, ``chip_smoke.py``,
``bench.py``): ``JAX_COMPILATION_CACHE_DIR`` decides when it is set —
JAX reads it itself and no directory is set in code — and otherwise the
cache is ``<checkout>/.jax_cache``. The path is part of the cache key's
lookup, so it is never derived from a temporary name, a pid or the time:
a directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

_DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Every executable is cached, however quickly it compiled, so a second
    run of the same command in the same checkout compiles nothing it
    compiled before."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
