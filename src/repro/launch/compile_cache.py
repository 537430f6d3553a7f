"""JAX's persistent compilation cache for the launchers and the chip smoke.

A full-width step takes tens of seconds to compile; the cache lets the
next process on the same machine skip that.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

# One fixed directory inside the checkout (src/repro/launch -> checkout).
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing is set here. Otherwise the cache lives in :data:`CACHE_DIR`,
    never a temp, pid- or time-derived name, so a later run finds it again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
