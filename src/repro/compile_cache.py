"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, the benchmarks, the examples) call
:func:`enable` once at start-up; importing the library never does, so an
application that embeds it keeps its own cache settings.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
directory and nothing else is set.  Otherwise the cache lives at the
fixed ``.jax_cache/`` beside ``src/``, never a per-run path, so a later
run finds what an earlier one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the fixed fallback location (``<repo>/.jax_cache``)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; return the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
