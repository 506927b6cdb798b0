"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the serve and train launchers, the
benchmark CLIs) call :func:`configure_compile_cache` once, before their
first compile.  Tests never do: a cache of programs compiled for a
described TPU cannot be read back on the CPU.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["configure_compile_cache", "DEFAULT_CACHE_DIR"]

# A fixed path: the directory is part of what the cache is found by, so
# it is never built from a temporary name, a pid or the time.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn the cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is changed here; otherwise the cache goes to
    ``<repo root>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
