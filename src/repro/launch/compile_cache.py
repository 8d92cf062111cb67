"""JAX's persistent compilation cache, placed where a later run finds it.

A cached executable is keyed (among other things) by the cache path, so
the directory must not move between runs: it is either the one the
environment names in ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that
variable itself, and nothing here overrides it) or the fixed
``.jax_cache/`` at the root of the checkout (listed in ``.gitignore``).
Entry points call ``enable_compile_cache()`` once at start-up; importing
this module changes nothing.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
