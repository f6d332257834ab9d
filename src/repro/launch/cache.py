"""Persistent XLA compile cache shared by the entry points.

Every entry point (``chip_smoke.py``, ``launch/train.py``,
``launch/serve.py``, the bench scripts) calls :func:`enable_compile_cache`
before its first compile; library code and tests never do.  The cache key
includes its directory, so the fallback is one fixed path inside the
repository.
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep compiled executables across processes.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and this
    sets nothing; otherwise the cache lives in ``<repo>/.jax_cache``.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
