"""Chunk-prefill attention at an offset into a paged KV pool — Pallas TPU.

``paged_gqa_prefill`` is the multi-token sibling of
``gqa_decode.paged_gqa_decode``: one grid step per (row, kv-head,
logical-block) streams a PHYSICAL pool block through VMEM via the
scalar-prefetched block table, masked to the resident prefix
``[0, offset)``, with online-softmax accumulators in scratch for the
whole chunk's query rows at once.  The chunk's own K/V (positions
``[offset, length)``) is passed explicitly and folded on the final
block step with the causal intra-chunk mask — flash-style chunk
self-attention fused with the masked pool read, so chunked prefill is
a genuinely different ACCEL build instead of the XLA gather fallback.

Query rows arrive flattened (chunk token major, GQA group rank minor):
row ``r`` is chunk token ``r // group`` at group rank ``r % group``.
Bucket-padding chunk columns (``>= length - offset``) and pool
positions ``>= offset`` are masked to NEG_INF; their contribution
washes out exactly in the final correction (``exp(NEG_INF - m)``
underflows to 0.0), the same argument the decode kernel and the
bucketed dense prefill rely on.

``paged_gqa_prefill_int8`` streams an int8 pool plus its parallel
per-token f32 scale planes through the same block table and
dequantises in VMEM.  Oracles: ``ref.paged_prefill_attention_ref`` /
``ref.paged_prefill_attention_int8_ref``.

This kernel IS the speculative-decode verify kernel: verifying k
drafted tokens against the target model is chunk prefill at offset
with W = k (the "chunk" is the drafted span, the pool holds the
committed prefix).  ``ops.paged_gqa_verify`` / ``paged_gqa_verify_int8``
re-export the same body under a distinct name so the runtime registers
verify as its own HOST/ACCEL binary; the oracles above cover both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gqa_decode import head_column, heads_major, scale_tiles

NEG_INF = -1e30


def _prefill_accumulate(q_ref, off_ref, len_ref, kn_ref, vn_ref, o_ref,
                        m_scr, l_scr, acc_scr, k, v, *, block_size: int,
                        nbt: int, scale: float, group: int):
    """Shared online-softmax body of the paged prefill kernels.

    ``k``/``v`` are this grid step's already-dequantised (block_size, hd)
    f32 planes — the f32 and int8 variants differ ONLY in the
    dequantise step.  The scratch
    accumulators carry one (W*group, …) online softmax for the whole
    chunk; the final block step folds the chunk's causal self-attention.
    """
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)               # (WG, hd)
    off = off_ref[b]                                  # pool valid on [0, off)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    s = jnp.where(kpos < off, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nbt - 1)
    def _finish():
        # fold the chunk itself: positions [offset, length), causal
        kn = kn_ref[0, 0].astype(jnp.float32)         # (W, hd)
        vn = vn_ref[0, 0].astype(jnp.float32)
        W = kn.shape[0]
        WG = q.shape[0]
        s_cur = jax.lax.dot_general(q, kn, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
        qi = jax.lax.broadcasted_iota(jnp.int32, (WG, W), 0) // group
        kj = jax.lax.broadcasted_iota(jnp.int32, (WG, W), 1)
        n_real = len_ref[b] - off                     # real chunk width
        s_cur = jnp.where((kj <= qi) & (kj < n_real), s_cur, NEG_INF)
        m_prev = m_scr[...]
        m_fin = jnp.maximum(m_prev, jnp.max(s_cur, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_fin)
        p_cur = jnp.exp(s_cur - m_fin)
        l_fin = l_scr[...] * corr + jnp.sum(p_cur, axis=-1, keepdims=True)
        acc = acc_scr[...] * corr + jax.lax.dot_general(
            p_cur, vn, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0, 0] = (acc / jnp.maximum(l_fin, 1e-20)).astype(o_ref.dtype)


def _prefill_kernel(tbl_ref, off_ref, len_ref, q_ref, k_ref, v_ref, kn_ref,
                    vn_ref, o_ref, m_scr, l_scr, acc_scr, *, block_size: int,
                    nbt: int, scale: float, group: int):
    del tbl_ref
    _prefill_accumulate(q_ref, off_ref, len_ref, kn_ref, vn_ref, o_ref,
                        m_scr, l_scr, acc_scr,
                        k_ref[0, 0].astype(jnp.float32),
                        v_ref[0, 0].astype(jnp.float32),
                        block_size=block_size, nbt=nbt, scale=scale,
                        group=group)


def _prefill_int8_kernel(tbl_ref, off_ref, len_ref, q_ref, k_ref, ks_ref,
                         v_ref, vs_ref, kn_ref, vn_ref, o_ref, m_scr, l_scr,
                         acc_scr, *, block_size: int, nbt: int, scale: float,
                         group: int):
    """Int8-dequantising variant: block + its (block_size, KV) f32 scale
    tile stream through the SAME block-table index map; dequantisation
    is one broadcast multiply by this head's scale column in VMEM.  The
    chunk's ``kn``/``vn`` stay full precision (they are quantised only
    when scattered into the pool)."""
    del tbl_ref
    h = pl.program_id(1)
    _prefill_accumulate(q_ref, off_ref, len_ref, kn_ref, vn_ref, o_ref,
                        m_scr, l_scr, acc_scr,
                        k_ref[0, 0].astype(jnp.float32)
                        * head_column(ks_ref[0], h),
                        v_ref[0, 0].astype(jnp.float32)
                        * head_column(vs_ref[0], h),
                        block_size=block_size, nbt=nbt, scale=scale,
                        group=group)


def _prefill_call(kernel, extra_specs, q, tables, offsets, lengths,
                  *operands, name: str, interpret: bool):
    """Shared ``pallas_call`` of the paged prefill kernels: grid
    ``(B, KV, NBT)``; ``operands`` are the heads-major page planes (and
    scale tiles, with ``extra_specs`` their block specs) followed by the
    chunk's heads-major k/v (B, KV, W, hd).  ``name`` is the kernel's
    name in the compiled program and in a device trace."""
    B, KV, WG, hd = q.shape
    block_size = operands[0].shape[2]
    W = operands[-1].shape[2]
    page_spec = pl.BlockSpec((1, 1, block_size, hd),
                             lambda b, h, j, t, o, n: (t[b, j], h, 0, 0))
    tok_spec = pl.BlockSpec((1, 1, W, hd),
                            lambda b, h, j, t, o, n: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                # tables, offsets, lengths
        grid=(B, KV, tables.shape[1]),
        in_specs=[
            pl.BlockSpec((1, 1, WG, hd),
                         lambda b, h, j, t, o, n: (b, h, 0, 0)),
            *extra_specs(page_spec), tok_spec, tok_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, WG, hd),
                               lambda b, h, j, t, o, n: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((WG, 1), jnp.float32),
            pltpu.VMEM((WG, 1), jnp.float32),
            pltpu.VMEM((WG, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, WG, hd), q.dtype),
        interpret=interpret,
        name=name,
    )(tables.astype(jnp.int32), offsets.astype(jnp.int32),
      lengths.astype(jnp.int32), q, *operands)


def paged_gqa_prefill(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      k_new: jax.Array, v_new: jax.Array, tables: jax.Array,
                      offsets: jax.Array, lengths: jax.Array, *, group: int,
                      interpret: bool = False) -> jax.Array:
    """q: (B, W*G, …) chunk queries flattened (token major, group-rank
    minor) and grouped per kv head as (B, KV, W*G, hd);
    k_pages/v_pages: (NP, BS, KV, hd) physical block pool; k_new/v_new:
    (B, W, KV, hd) the chunk's own K/V; tables: (B, NBT) int32 physical
    block ids; offsets/lengths: (B,) int32.

    Attends each chunk query over pool positions [0, offsets[b]) plus
    the chunk's causally-preceding real columns ([offset, length) in
    absolute positions).  offsets == 0 reduces to plain causal chunk
    self-attention (no pool read survives the final correction), so the
    first chunk of an uncached prompt is well-defined.
    """
    kernel = functools.partial(_prefill_kernel, block_size=k_pages.shape[1],
                               nbt=tables.shape[1],
                               scale=1.0 / np.sqrt(q.shape[-1]), group=group)
    return _prefill_call(kernel, lambda page: (page, page), q, tables,
                         offsets, lengths,
                         heads_major(k_pages), heads_major(v_pages),
                         heads_major(k_new), heads_major(v_new),
                         name="paged_gqa_prefill", interpret=interpret)


def paged_gqa_prefill_int8(q: jax.Array, k_pages: jax.Array,
                           k_scale: jax.Array, v_pages: jax.Array,
                           v_scale: jax.Array, k_new: jax.Array,
                           v_new: jax.Array, tables: jax.Array,
                           offsets: jax.Array, lengths: jax.Array, *,
                           group: int, interpret: bool = False) -> jax.Array:
    """``paged_gqa_prefill`` over an int8 pool with per-token scales.

    k_pages/v_pages: (NP, BS, KV, hd) int8; k_scale/v_scale:
    (NP, BS, KV, 1) f32 symmetric per-(token, kv-head) scales.  Scale
    tiles ride the SAME scalar-prefetched block table as the int8
    blocks; q and the chunk's k_new/v_new stay full precision.
    """
    BS, KV = k_pages.shape[1], k_pages.shape[2]
    kernel = functools.partial(_prefill_int8_kernel, block_size=BS,
                               nbt=tables.shape[1],
                               scale=1.0 / np.sqrt(q.shape[-1]), group=group)
    scale_spec = pl.BlockSpec((1, BS, KV),
                              lambda b, h, j, t, o, n: (t[b, j], 0, 0))
    return _prefill_call(kernel,
                         lambda page: (page, scale_spec, page, scale_spec),
                         q, tables, offsets, lengths,
                         heads_major(k_pages), scale_tiles(k_scale),
                         heads_major(v_pages), scale_tiles(v_scale),
                         heads_major(k_new), heads_major(v_new),
                         name="paged_gqa_prefill_int8", interpret=interpret)
