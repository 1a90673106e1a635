"""JAX's persistent compilation cache for the launchers.

``enable_compile_cache()`` is called from the ``main()`` of each entry point
(``launch/serve.py``, ``launch/train.py``, ``chip_smoke.py``), never on
import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here.  Otherwise the cache goes to ``<repo>/.jax_cache``: a
fixed path, because the directory is part of what a later run must find
again, so a second run in the same checkout reads what the first compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
