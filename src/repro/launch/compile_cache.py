"""Where the entry points that run on the chip keep compiled programs.

Compiling the train step or the engine core for the chip takes tens of
seconds; JAX's persistent compilation cache lets a later process of the
same checkout load them instead.  The cache directory is part of every
entry's key, so it must not move between runs: no temp, pid or
time-derived path.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    nothing is set here.  Otherwise the cache is ``<repo>/.jax_cache``
    (listed in ``.gitignore``).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
