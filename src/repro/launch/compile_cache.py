"""Persistent JAX compilation cache for the command-line entry points.

A cold run on the chip recompiles every train step, serve bucket and store
migrate shape; the persistent cache lets the next run of the same checkout
load them instead.  ``use_compile_cache`` is called first in each CLI
``main`` and in ``chip_smoke.py`` — never on import, and never by the tests.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: a fixed path, because the directory is part of the
# cache key — a path built from a pid, a temp name or the time never hits
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    it.  ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    left alone; otherwise the cache goes to ``<checkout>/.jax_cache``."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
