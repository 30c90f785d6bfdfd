"""Seeded random weights, made on the device in one jitted call.

The tree is the architecture's ``shapes`` (``bench/arch``), laid out the
way the serving engine takes its parameters, and is served in bfloat16.
The benchmark makes it; the program only receives it, and the plain
reference in ``bench/reference.py`` reads the same arrays.
"""
from __future__ import annotations

import numpy as np

from bench import arch

# norm weights are 1 + NORM_NOISE * N(0, 1), so a reference that ignored
# them, or applied them to the wrong side, would disagree
NORM_NOISE = 0.1
EMBED_STD = 0.02


def seed_words(seed: int, n: int = 2) -> list[int]:
    """``n`` uint32 words hashed from any non-negative integer seed
    (``jax.random.PRNGKey`` keeps only 32 bits of a larger one)."""
    return [int(w) for w in np.random.SeedSequence(int(seed)).generate_state(n)]


def jax_key(seed: int, stream: int = 0):
    import jax
    hi, lo = seed_words(seed)
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(lo), hi),
                              stream)


def shapes(m: dict) -> dict:
    """(shape, dtype, init std or 'norm') for every leaf of the tree, by
    the module of the architecture (``bench/arch``)."""
    return arch.load(m["model_type"]).shapes(m)


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def param_count(m: dict) -> int:
    import jax
    return sum(int(np.prod(s)) for s, _, _ in
               jax.tree.leaves(shapes(m), is_leaf=_is_leaf))


def make(m: dict, seed: int, device=None):
    """The whole tree from ``seed``, generated on ``device`` (default:
    the first) by one jitted call."""
    import jax
    import jax.numpy as jnp
    spec = shapes(m)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, dtype, std) in zip(keys, leaves):
            if std == "norm":
                x = 1.0 + NORM_NOISE * jax.random.normal(k, shape, jnp.float32)
            else:
                x = std * jax.random.normal(k, shape, jnp.float32)
            out.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    device = device or jax.devices()[0]
    with jax.default_device(device):
        key = jax_key(seed)
    params = jax.jit(build, out_shardings=jax.sharding.SingleDeviceSharding(
        device))(key)
    return jax.block_until_ready(params)
