"""Where JAX's persistent compilation cache lives.

Entry points call :func:`use_compile_cache` first thing in ``main()``
(never at import), so a second cold run of the same program reads its
compiled executables back instead of compiling again. The directory is
fixed because it is part of the cache key: a cache that moves between
runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache — this file sits at <checkout>/src/repro/.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that variable
    itself and this sets nothing; otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
