"""JAX's persistent compilation cache, at one fixed place per checkout.

The launchers and ``chip_smoke.py`` call ``enable()`` at start-up.  A
cache only hits when its directory stays the same from one run to the
next, so there is no temporary, per-process or per-run path here.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Where JAX keeps compiled programs for this process.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and this
    sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``
    (listed in ``.gitignore``)."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
