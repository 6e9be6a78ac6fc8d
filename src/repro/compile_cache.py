"""Persistent XLA compilation cache for the entry points.

Compiling the ACK programs at serving widths takes seconds per variant, so
entry points (``chip_smoke.py``, the examples, the benchmarks) keep JAX's
persistent compilation cache on. The library never turns it on by itself:
importing ``repro`` leaves JAX's configuration alone.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and this module
  sets no other directory.
* otherwise: ``<checkout>/.jax_cache`` (gitignored). The path is fixed —
  never a temporary name, a process id or a time — because the path is
  part of what a later process must find again.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(checkout: str) -> str:
    """Turn the persistent cache on before the first compile; returns the
    directory in use. ``checkout`` is the root of the repository the
    caller runs from (the fallback cache lives there)."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = os.path.join(os.path.abspath(checkout), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however quick: the kernels alone compile in
    # well under JAX's default one-second threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


__all__ = ["enable_compile_cache"]
