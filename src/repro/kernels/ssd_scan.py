"""Chunked SSD (Mamba2) scan — Pallas TPU kernel.

Grid ``(batch*heads, chunks)`` with the chunk axis innermost/sequential;
the running inter-chunk state (headdim x dstate) lives in VMEM scratch
and is carried across chunk steps — the TPU analogue of the Mamba2
"state passing" CUDA kernel.  Per chunk we compute the intra-chunk
semiseparable (quadratic) term on the MXU and the state contribution,
then update the carried state.

Inputs are pre-projected/pre-conv'd (x, dt, B, C) per head; the oracle
is ``ref.ssd_ref`` (== models.ssm.ssd_chunked).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, sfin_ref,
                state_scr, *, chunk: int, nchunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)          # (l, p)
    dt = dt_ref[0].astype(jnp.float32)        # (l, 1)
    A = a_ref[0, 0]                           # scalar decay rate (negative)
    Bm = b_ref[0].astype(jnp.float32)         # (l, n)
    Cm = c_ref[0].astype(jnp.float32)         # (l, n)

    xd = x * dt                               # dt-discretised input
    a = A * dt[:, 0]                          # (l,) log-decay per step
    cs = jnp.cumsum(a)                        # inclusive

    # intra-chunk: L[i,j] = exp(cs_i - cs_j) for i >= j
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    Lmat = jnp.where(li >= lj, jnp.exp(cs[:, None] - cs[None, :]), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(Lmat * scores, xd, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # previous-state contribution: C_i . S_prev * exp(cs_i)
    s_prev = state_scr[...]                   # (p, n)
    y += jnp.exp(cs)[:, None] * jax.lax.dot_general(
        Cm, s_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: S = S * exp(cs_last) + sum_i exp(cs_last - cs_i) x_i B_i^T
    decay_out = jnp.exp(cs[-1] - cs)          # (l,)
    new_state = s_prev * jnp.exp(cs[-1]) + jax.lax.dot_general(
        xd * decay_out[:, None], Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_scr[...] = new_state

    o_ref[0] = y.astype(o_ref.dtype)

    @pl.when(ci == nchunks - 1)
    def _finish():
        sfin_ref[0] = new_state.astype(sfin_ref.dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, *, chunk: int = 256,
             interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """x: (BH, S, P); dt: (BH, S); A: (BH,); Bm/Cm: (BH, S, N).

    Returns (y: (BH, S, P), final_state: (BH, P, N)).
    BH = batch * heads (B/C broadcast over heads is done by the wrapper).
    """
    BH, S, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk, nchunks=nc)
    y, sfin = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1), lambda b, c: (b, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, P, N), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), x.dtype),
            jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(x, dt[..., None], A[:, None], Bm, Cm)
    return y, sfin
