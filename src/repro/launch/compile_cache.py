"""One persistent XLA compilation cache for every entry point.

``enable()`` is called at start-up by ``chip_smoke.py``, the launchers,
the examples and the benchmarks — never while a module is imported.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there and
nothing here overrides it; otherwise the cache lives at a fixed path
inside the checkout, ``<repo>/.jax_cache`` (gitignored).  The path is
fixed because it is part of what lets a later run find the entries.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(ENV) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
