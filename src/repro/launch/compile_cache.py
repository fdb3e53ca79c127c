"""Where JAX keeps its persistent compilation cache.

A restarted or resumed run that finds its compiled step in the cache skips
the compile. The cache only hits when the directory stays put, so the path
is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads that variable itself, and nothing is set here), otherwise
``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Place the persistent compilation cache; call before the first
    compile. Returns the directory in use."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
