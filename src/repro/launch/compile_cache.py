"""Placement of JAX's persistent compilation cache for the entry points.

Called from ``main()`` of the serve and train drivers and from
``chip_smoke.py``, never at import: importing the library leaves JAX's
configuration alone.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CHECKOUT", "configure_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself
    and nothing is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache`` (git-ignored), a fixed path so that every
    process started from this checkout finds the programs the earlier ones
    compiled.  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
