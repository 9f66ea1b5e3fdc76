"""Where compiled programs are kept between processes.

One root, placed from outside: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (jax reads it by itself — nothing is set in code),
else the fixed ``<checkout>/.cache/jax``.  The path is part of jax's
cache key, so it is never a temp dir, a pid or a timestamp: a directory
that moves never hits.  Entry points that touch the chip call
:func:`enable_compile_cache` before their first compile; what they
persist besides XLA's own cache (the serving AOT programs) goes under
the same root, so carrying that one directory over carries everything.
"""
from __future__ import annotations

import os

import jax

__all__ = ["cache_root", "compile_cache_dir", "enable_compile_cache",
           "serving_aot_dir"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_root():
    """``<checkout>/.cache`` — build outputs and caches the program makes
    at run time (listed in ``.gitignore``)."""
    return os.path.join(_CHECKOUT, ".cache")


def compile_cache_dir():
    """The persistent XLA compilation cache directory."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        cache_root(), "jax")


def serving_aot_dir():
    """The ``AOTProgramCache`` directory of the chip-facing entry points."""
    return os.path.join(compile_cache_dir(), "serving_aot")


def enable_compile_cache():
    """Turn the persistent compilation cache on; returns its directory.

    Every program is kept, however quick its compile: a second run
    against a kept cache then compiles nothing at all.
    """
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
