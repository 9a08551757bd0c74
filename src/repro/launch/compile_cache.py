"""Where JAX's persistent compilation cache lives for this checkout.

Entry points call :func:`use_compile_cache` from their ``main``, before
their first compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).  A fixed path, never
#: a temporary one: a later run on the same checkout finds its entries.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
