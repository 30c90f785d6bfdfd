"""Grouped (per-expert) matmul — Pallas TPU kernel for the MoE ACCEL path.

Computes ``out[e] = x[e] @ w[e]`` over capacity-padded expert buffers
with a per-expert valid-row count (``group_sizes``): rows past the group
size are masked to zero so dropped-token slots cost no accuracy (they
still cost flops — the buffers are rectangular, which is what the MXU
wants; MegaBlocks-style block-sparsity is a further step recorded in
EXPERIMENTS.md §Perf).

Grid ``(E, C/bc, F/bf, D/bd)`` with a VMEM fp32 accumulator carried over
the innermost (contraction) axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, gs_ref, o_ref, acc_scr, *, block_c: int,
                nd: int):
    di = pl.program_id(3)
    ci = pl.program_id(1)

    @pl.when(di == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0].astype(jnp.float32)          # (bc, bd)
    w = w_ref[0].astype(jnp.float32)          # (bd, bf)
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    rows = ci * block_c + jax.lax.broadcasted_iota(jnp.int32, acc_scr.shape, 0)

    @pl.when(di == nd - 1)
    def _finish():
        valid = rows < gs_ref[0]
        o_ref[0] = jnp.where(valid, acc_scr[...], 0.0).astype(o_ref.dtype)


def grouped_matmul(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                   block_c: int = 128, block_f: int = 128, block_d: int = 256,
                   interpret: bool = False) -> jax.Array:
    """x: (E, C, D); w: (E, D, F); group_sizes: (E,) int32 -> (E, C, F)."""
    E, C, D = x.shape
    F = w.shape[-1]
    block_c = min(block_c, C)
    block_f = min(block_f, F)
    block_d = min(block_d, D)
    assert C % block_c == 0 and F % block_f == 0 and D % block_d == 0
    nc, nf, nd = C // block_c, F // block_f, D // block_d

    kernel = functools.partial(_gmm_kernel, block_c=block_c, nd=nd)
    return pl.pallas_call(
        kernel,
        grid=(E, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1, block_c, block_d),
                         lambda e, ci, fi, di: (e, ci, di)),
            pl.BlockSpec((1, block_d, block_f),
                         lambda e, ci, fi, di: (e, di, fi)),
            pl.BlockSpec((1,), lambda e, ci, fi, di: (e,)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e, ci, fi, di: (e, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        interpret=interpret,
        name="grouped_matmul",
    )(x, w, group_sizes.astype(jnp.int32))
