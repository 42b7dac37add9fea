"""Where JAX's persistent compilation cache lives.

Entry points that compile real work (``chip_smoke.py``, the benchmark
CLIs) call :func:`place_compile_cache` once, before anything compiles.
Library import and the tests never do: a test run keeps whatever cache
its environment chose.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set — JAX reads it
itself, and nothing here sets another directory.  Otherwise the cache
goes to ``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The path
is fixed on purpose: it is part of what the cache is keyed on, so a
temp name, a pid or a timestamp would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root (this file is ``<root>/src/repro/runtime/...``)
CHECKOUT = Path(__file__).resolve().parents[3]


def place_compile_cache() -> str:
    """Point the persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
