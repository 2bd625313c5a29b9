"""Persistent XLA compilation cache.

The engine's compiled loop takes tens of seconds to build on a TPU
backend; the reference pays this cost once at BUILD time — its
binaries ship AOT-compiled kernels (pfsp/makefile nvcc/hipcc
invocations). JAX's persistent compilation cache is the equivalent:
the first process compiles and writes the executable to disk, every
later process with the same program, jaxlib and flags loads it.
Enabled by every entry point (CLI, bench, chip_smoke, tools) via
:func:`enable`.

Placement comes from outside through JAX's own variables:
``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it; this module
then sets no path), else a fixed directory inside the checkout,
:data:`DEFAULT_DIR`. The directory is part of the cache's key, so it
must not move between runs. ``JAX_ENABLE_COMPILATION_CACHE=false``
turns the cache off.
"""

from __future__ import annotations

import contextlib
import os
import pathlib

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str | None:
    """Turn on JAX's persistent compilation cache. Returns the directory
    in use, or None when the cache is turned off."""
    import jax
    if not jax.config.jax_enable_compilation_cache:
        return None
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


@contextlib.contextmanager
def disabled():
    """Compile without the persistent cache inside the block: nothing is
    read from it or written to it (a cold compile that stays cold)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
