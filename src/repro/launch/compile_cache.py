"""Where JAX keeps its persistent compilation cache.

A run finds what an earlier run cached only at the same path, so the
directory never depends on a temp dir, pid or time:
``$JAX_COMPILATION_CACHE_DIR`` where it is set (JAX reads it on its own),
else ``.jax_cache`` at the root of this checkout.
"""

from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call at the start of an entry point's ``main``, before the first
    compile, and never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
