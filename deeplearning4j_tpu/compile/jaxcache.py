"""Where JAX's persistent compilation cache lives for the chip entry points.

Every call of the chip tool is a new machine, and a cold ResNet-50 +
BERT-base + serving-ladder compile is minutes.  JAX's own cache (keyed on
the HLO, the compile options and the backend version) makes the second
process on the same disk skip that, but only if both processes name the
same directory: the path is part of nothing JAX hashes, yet a directory
that moves (``tempfile``, a pid, the time) is never found again.

The chip entry points (``chip_smoke.py``, ``benchmark/run.py``) call
:func:`enable_compile_cache` before their first compilation.  Tests do
not.  This is the only place in the repo that sets
``jax_compilation_cache_dir``.  (``AotCache`` in :mod:`.aotcache` is a
different thing — serialized executables keyed by model topology, under
its own ``DL4J_TPU_AOT_CACHE_DIR`` — and is not enabled here.)
"""
from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

#: ``<checkout>/.jax_cache`` — fixed, git-ignored, inside the tree the chip
#: tool copies, so what one process compiles the next one finds
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, so nothing is
    changed and that directory is returned.  Unset: the cache goes to
    :data:`DEFAULT_CACHE_DIR`.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
