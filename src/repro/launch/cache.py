"""Where compiled programs persist between processes.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set, this module
sets nothing. Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, so the next process finds what this one wrote; a temp name,
a pid or a time in the path would start every run cold.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
