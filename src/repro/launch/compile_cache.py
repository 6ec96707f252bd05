"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (``rl_train``, ``policy_serve``,
``benchmarks/run.py``, ``chip_smoke.py``, the test suite):
``JAX_COMPILATION_CACHE_DIR`` when it is set — JAX reads the variable
itself, and no other directory is set in code — otherwise the fixed
directory ``.jax_cache/`` at the root of the checkout (gitignored). The
cache directory is part of what a later run looks up, so the fallback is
a fixed path, never one made from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; -> its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
