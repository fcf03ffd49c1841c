"""JAX's persistent compilation cache, switched on in one place.

Every entry point that compiles real programs (``chip_smoke.py``, the
examples, ``benchmarks/run.py``) calls ``enable_compile_cache`` first, so a
second process of the same checkout loads the compiled executables instead
of recompiling them.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache: a fixed path (listed in .gitignore), never built
# from a temp name, a pid or the time — the directory is part of what a
# later process must find again
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache lives in
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
