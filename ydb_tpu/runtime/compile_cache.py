"""Persistent XLA compilation cache placement.

One call, made by the process entry points (``python -m ydb_tpu.cli``,
``chip_smoke.py``)
before their first compile and by nothing at import time. Tests do not
call it: ``analysis/syncsan.py`` counts compiles from JAX's
``backend_compile_duration`` event, and a statement that a test expects
to compile must not start hitting a cache.

The directory is part of the cache's key, so it never moves: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
directory is set in code; otherwise the cache sits at
``<checkout>/.jax_cache`` (git-ignored).
"""

from __future__ import annotations

import os
import pathlib


def configure() -> str:
    """Switch the persistent compile cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(pathlib.Path(__file__).resolve().parents[2]
                   / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # a statement is many small programs (scan partial, combine, final,
    # staging concats): cache them all, not only the slow ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
