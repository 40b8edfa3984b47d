"""Persistent XLA compilation cache placement.

Entry points call ``enable_compile_cache()`` once, before their first
compile; nothing calls it at import time. Where the environment sets
``JAX_COMPILATION_CACHE_DIR``, JAX has already read it and that setting
stands. Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed
path, because the directory is part of what a later run must find again
(a temp name, pid or timestamp would never hit).
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return the directory in effect."""
    import jax
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
