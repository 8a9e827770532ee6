"""Where the entry points keep JAX's persistent compilation cache.

Called by the entry points (``launch/train.py``, ``launch/serve.py``,
``chip_smoke.py``), never at import: the tests run without the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# one fixed, git-ignored directory inside the checkout: a cache only
# hits when its path is the same from one run to the next
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which
    reads it itself; nothing here overrides it.  Otherwise the cache goes
    to ``<checkout>/.jax_cache``."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
