"""Fused causal flash attention — Pallas TPU kernel.

Canonical TPU pattern: 3-D grid ``(batch*heads, q_blocks, kv_blocks)``
with the kv dimension innermost (TPU grids iterate the last axis
sequentially), online-softmax accumulators in VMEM scratch, output
written on the final kv step.  BlockSpecs tile Q/K/V into
``(1, block, head_dim)`` VMEM windows; ``head_dim`` is MXU-lane aligned
by the ops wrapper (pads to 128 when needed).

This is the ACCEL ("hardware kernel") implementation of the attention
function in Xar-Trek terms; the oracle is ``ref.attention_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int, kv_blocks: int,
                  causal: bool, kv_len: int, q_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                     # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                     # (bk, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                       (block_q, block_k), 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                       (block_q, block_k), 1)
        s = jnp.where(kpos <= qpos + (kv_len - q_len), s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == kv_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-20)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 256,
                    block_k: int = 256, interpret: bool = False) -> jax.Array:
    """q: (BH, S, hd); k, v: (BH, T, hd) -> (BH, S, hd)."""
    BH, S, hd = q.shape
    T = k.shape[1]
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    nq, nk = S // block_q, T // block_k
    scale = 1.0 / np.sqrt(hd)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        kv_blocks=nk, causal=causal, kv_len=T, q_len=S)

    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
