"""Persistent compilation cache for the command-line entry points.

The entry points (``chip_smoke.py``, the ``repro.launch`` command-line
modules and the ``benchmarks/`` scripts) call :func:`enable` once from
their ``main`` so that a program compiled by one run is found again by the
next.  Importing the library never turns the cache on, and neither do the
tests.

Entry points that report a compile time leave the cache off, since a
cache hit would be read as a compile: ``repro.launch.dryrun`` and
``benchmarks/hillclimb.py`` (``compile_s``), and ``benchmarks/run.py``
(``runtime_scaling``'s ``greedy_cold_s`` and each benchmark's total wall).
The other benchmark scripts time only after an untimed warm-up.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR``, when it is set: jax reads the variable
  itself, and nothing here overrides it;
* otherwise ``<checkout>/.jax_cache`` (git-ignored).  The path is fixed —
  never derived from a temp name, a pid or the time — because a cache
  directory that moves between runs never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
