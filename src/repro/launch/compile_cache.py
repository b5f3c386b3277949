"""Where JAX's persistent compilation cache lives.

Entry points call ``enable_compile_cache()`` once, before their first
compile; importing this module changes nothing.  When the environment
sets ``JAX_COMPILATION_CACHE_DIR``, JAX reads it itself and this sets no
other directory.  Otherwise the cache goes to ``.jax_cache`` at the root
of the checkout: a fixed path, so a later run of the same checkout finds
its entries (the directory is part of the cache key).  That root is
found from this file's place in the ``src/`` layout, so without the
variable it works only from a source checkout: an installed package
would share one directory between checkouts, and is refused.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
CHECKOUT_CACHE = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env  # JAX took it from the environment at start-up
    if not (CHECKOUT / "pyproject.toml").is_file():
        raise RuntimeError(f"{CHECKOUT} is not a checkout of this repo; "
                           "set JAX_COMPILATION_CACHE_DIR")
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
