"""JAX's persistent compilation cache, placed from outside or in the checkout.

Entry points (``repro.launch.train``, ``repro.launch.serve``,
``chip_smoke.py``) call :func:`enable_compile_cache` at start-up, never at
import, so tests and library users get no cache unless they ask for one.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing here
  touches the setting, and no other directory is used.
* otherwise: ``<checkout>/.jax_cache`` (git-ignored).  The path is fixed —
  it is part of what a later run must find again.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
