"""Where JAX keeps its persistent compilation cache for this repository,
and the count of the time spent filling it."""
from __future__ import annotations

import os
import pathlib

import jax

from repro.obs import compiles

# A fixed path inside the checkout (git-ignored): compiled programs are
# found again only by a run that looks in the same place.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX already uses it and no
    other directory is set. Otherwise the cache goes to CACHE_DIR. Also
    starts the process's compile counter (`repro.obs.compiles`). Call
    this before the process compiles anything.
    """
    compiles.install()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
