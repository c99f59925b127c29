"""JAX's persistent compile cache for the command-line entry points.

``enable_compile_cache()`` is called once by each entry point that a user
runs (``python -m repro.launch.cli``, ``python -m repro.launch.train``,
``python chip_smoke.py``) before its first
compile.  It is never called when a module is imported, so library users
and the tests keep JAX's own default.

* With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself
  and nothing is set here.
* Otherwise the cache lives at ``<checkout>/.jax_cache``.  The path is
  fixed on purpose: it is part of what a later process must find, so it
  never comes from a temporary directory, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
