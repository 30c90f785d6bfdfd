"""Production mesh construction (assignment-required entry point).

Functions only — importing this module never touches jax device state.
"""
from __future__ import annotations

import jax

from repro.parallel.mesh import make_auto_mesh


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)
