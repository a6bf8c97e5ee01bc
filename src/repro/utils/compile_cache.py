"""Placement of JAX's persistent compilation cache for the entry points.

``enable_compile_cache()`` is called from the ``main()`` of each command
(``chip_smoke.py``, ``benchmarks/run.py``, ``python -m repro.serve``,
``python -m repro.tune``), never at import, so importing the library
changes no JAX setting.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it at start-up and keeps
  its cache there; this module sets no other path.
* unset: the cache goes to ``<checkout>/.jax_cache`` — a fixed path, so
  a later run from the same checkout finds what an earlier one compiled
  (the directory is git-ignored).
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV, "")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
