"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points that compile (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once before their first compile; importing
``repro`` never touches the cache.  The directory is fixed, because the
path is part of the cache key: a directory that moves between runs never
hits.
"""

from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads it
    itself) and nothing else is set; otherwise the cache lives in
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
