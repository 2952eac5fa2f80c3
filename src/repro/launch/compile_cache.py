"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, the serve and train launchers) call
:func:`use_compile_cache` once at start-up; importing ``repro`` never
does.  A cache hit needs the same directory from run to run, because the
path is part of the cache key, so the default is a fixed directory of
the checkout and never a temporary name.
"""
from __future__ import annotations

import os
import pathlib

import jax

# src/repro/launch/compile_cache.py -> the checkout's root
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    the cache lives there: nothing else is set.  Otherwise the cache goes
    to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
