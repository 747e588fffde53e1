"""Where entry-point scripts keep JAX's persistent compilation cache.

A cache hits only when its directory stays put, so the default is a
fixed path inside the checkout. Entry points (``chip_smoke.py``,
``examples/train_dfl.py``, ``benchmarks/run.py``) call
``use_compile_cache`` once at start-up; library import and the tests
never do.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its path.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
    itself and nothing here overrides it. Otherwise the cache is
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
