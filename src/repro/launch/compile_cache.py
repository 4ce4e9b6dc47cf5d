"""JAX's persistent compilation cache, at a path a later run finds again.

Entry points (``chip_smoke.py``, ``launch.serve``, ``launch.train``,
``benchmarks.run``) call :func:`enable_compile_cache` once, before they
compile anything.  Importing a module never turns the cache on, and the
tests never do.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, because the directory is part of what a cache hit needs.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Tuple

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Tuple[str, bool]:
    """Returns (cache directory, whether it came from the environment)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env, True
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE), False
