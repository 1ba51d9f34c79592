"""Where the entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Returns the cache directory in use.  ``JAX_COMPILATION_CACHE_DIR``,
    when set, is read by JAX itself and left alone; otherwise the cache goes
    to one fixed directory in the checkout, so a later run of the same
    checkout finds it again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
