"""Fused RMSNorm — Pallas TPU kernel.

Row-tiled: grid over row blocks, each block normalises (block_rows, d)
in VMEM with an fp32 reduction.  Simple but real: this is the smallest
"hardware kernel" in the bank and doubles as the KernelBank smoke
workload.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-5,
            block_rows: int = 128, interpret: bool = False) -> jax.Array:
    """x: (R, d); w: (d,) -> (R, d)."""
    R, d = x.shape
    block_rows = min(block_rows, R)
    assert R % block_rows == 0, (R, block_rows)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda r: (r, 0)),
            pl.BlockSpec((d,), lambda r: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((R, d), x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(x, w)
