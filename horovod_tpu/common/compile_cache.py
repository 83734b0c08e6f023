"""A persistent XLA compile cache whose place can be chosen from outside.

Compiling the decoder train step for a TPU takes tens of seconds, and a
cold process pays it again unless JAX's persistent compilation cache is
on.  The cache directory is part of nothing the program computes, but it
must not move between runs (a directory that moves never hits), so:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; this module
  touches nothing and sets no other directory;
* otherwise, on an accelerator — ``<checkout>/.jax_cache``, resolved from
  this package's own location: the same path from any working directory,
  never from ``tempfile``, a pid or a clock.  ``.gitignore`` lists it;
* otherwise, on the CPU backend — nothing.  XLA:CPU executables are built
  for the compiling host's CPU features, and a cache that sits in the tree
  travels with it to other machines (the chip tool copies the disk).

Called by the entry points that run jitted programs
(``horovod_tpu.jax.init``, ``serve/replica.py``).  It asks JAX for its
backend, so it runs after any ``jax.distributed.initialize``.
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["default_cache_dir", "enable_compile_cache"]

_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — beside the ``horovod_tpu`` package."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compile cache on where it pays; return the
    directory in force, or None when the cache is left off."""
    placed = os.environ.get(_ENV)
    if placed:
        return placed
    import jax

    if jax.default_backend() == "cpu":
        return None
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
