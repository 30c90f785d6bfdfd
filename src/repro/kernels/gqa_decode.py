"""Single-token decode attention over a long KV cache — Pallas TPU kernels.

Flash-decoding style: ``gqa_decode`` (grid ``(batch*heads, kv_blocks)``)
streams a dense cache through VMEM with online-softmax accumulators in
scratch (one q-row per program), masked at the live length.

``paged_gqa_decode`` is the block-table-aware variant for the paged
(vLLM-style) KV pool: the block table and per-row live lengths ride in
as scalar-prefetch operands, so each grid step's BlockSpec index map
dereferences ``table[b, j]`` and DMAs that PHYSICAL block from the pool
— the kernel walks a row's blocks in logical order without ever
materialising the gathered per-row cache.  The current token's K/V is
passed explicitly and folded into the online softmax on the final block
(write-then-attend: the pool contributes positions ``< length`` only).

``paged_gqa_decode_int8`` streams an int8 pool plus its parallel
per-token f32 scale planes through the same block table and
dequantises in-kernel (one broadcast multiply in VMEM) — the pool is
never materialised at full precision.

TPU tiling: a block's last two dims must be (8k, 128k) or the whole
array dims.  The pool's ``(NP, BS, KV, hd)`` ABI would put a 1-of-KV
slice in the second-minor dim, so the kernels see the pool as
``(NP, KV, BS, hd)`` — a page block is then one ``(BS, hd)`` plane —
and a block's scale planes as the whole ``(BS, KV)`` tile, from which
the kernel selects its kv head's column exactly.

These are the ACCEL variants of the decode hot function (the serve-path
analogue of the paper's hardware kernel); oracles:
``ref.decode_attention_ref`` / ``ref.paged_decode_attention_ref`` /
``ref.paged_decode_attention_int8_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, block_k: int, kv_blocks: int, scale: float):
    b = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (1, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    live = idx_ref[b]                                 # attend over [0, live]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    s = jnp.where(kpos <= live, s, NEG_INF)

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


def gqa_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
               index: jax.Array, *, block_k: int = 512,
               interpret: bool = False) -> jax.Array:
    """q: (BH, 1, hd); k_cache/v_cache: (BH, Smax, hd); index: () int32
    shared position or (BH,) per-row positions.

    Attends over cache positions [0, index].  BH = batch * q-heads with
    the cache already head-expanded by the ops wrapper.  The per-row
    positions ride in SMEM as a scalar-prefetch operand.
    """
    BH, _, hd = q.shape
    Smax = k_cache.shape[1]
    block_k = min(block_k, Smax)
    assert Smax % block_k == 0, (Smax, block_k)
    nk = Smax // block_k
    scale = 1.0 / np.sqrt(hd)

    kernel = functools.partial(_decode_kernel, block_k=block_k,
                               kv_blocks=nk, scale=scale)
    idx = jnp.broadcast_to(index.astype(jnp.int32).reshape(-1), (BH,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                # per-row live positions
        grid=(BH, nk),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda b, ki, n: (b, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, ki, n: (b, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, ki, n: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda b, ki, n: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, 1, hd), q.dtype),
        interpret=interpret,
        name="gqa_decode",
    )(idx, q, k_cache, v_cache)


# ------------------------------------------------------------ paged variant

def _paged_accumulate(q_ref, len_ref, kn_ref, vn_ref, o_ref, m_scr, l_scr,
                      acc_scr, k, v, *, block_size: int, nbt: int,
                      scale: float):
    """Shared online-softmax body of the paged decode kernels.

    ``k``/``v`` are this grid step's already-dequantised (block_size, hd)
    f32 planes — the f32 kernel passes the block verbatim, the int8
    kernel multiplies the streamed scale plane in first.  Keeping one
    body guarantees the two variants differ ONLY in the dequantise step.
    """
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)               # (G, hd)
    live = len_ref[b]                                 # pool valid on [0, live)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    s = jnp.where(kpos < live, s, NEG_INF)

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
        # fold the current token (position ``live``, not yet in the pool)
        kn = kn_ref[0, 0].astype(jnp.float32)         # (1, hd)
        vn = vn_ref[0, 0].astype(jnp.float32)
        s_cur = jax.lax.dot_general(q, kn, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
        m_prev = m_scr[...]
        m_fin = jnp.maximum(m_prev, s_cur)
        corr = jnp.exp(m_prev - m_fin)
        p_cur = jnp.exp(s_cur - m_fin)
        l_fin = l_scr[...] * corr + p_cur
        acc = acc_scr[...] * corr + p_cur * vn
        o_ref[0, 0] = (acc / jnp.maximum(l_fin, 1e-20)).astype(o_ref.dtype)


def head_column(scales, h):
    """(BS, KV) scale tile -> (BS, 1) column of kv head ``h`` (exact:
    one selected value plus zeros).  The whole tile is the block because
    a one-column block of a KV-wide array is not a legal TPU tiling."""
    lane = jax.lax.broadcasted_iota(jnp.int32, scales.shape, 1)
    return jnp.sum(jnp.where(lane == h, scales, 0.0), axis=1, keepdims=True)


def heads_major(pages: jax.Array) -> jax.Array:
    """(NP, BS, KV, hd) pool -> (NP, KV, BS, hd), the kernels' view: a
    page block is then one (BS, hd) plane, a legal TPU tiling."""
    return pages.transpose(0, 2, 1, 3)


def scale_tiles(scale: jax.Array) -> jax.Array:
    """(NP, BS, KV, 1) scale pool -> (NP, BS, KV) f32 tiles."""
    return scale[..., 0].astype(jnp.float32)


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref,
                  o_ref, m_scr, l_scr, acc_scr, *, block_size: int,
                  nbt: int, scale: float):
    """One (row, kv-head, logical-block) grid step.

    The BlockSpec index map already resolved ``tbl_ref[b, j]`` to the
    physical block, so ``k_ref``/``v_ref`` hold that block's
    (block_size, hd) plane; the kernel only masks and accumulates.
    """
    del tbl_ref
    _paged_accumulate(q_ref, len_ref, kn_ref, vn_ref, o_ref, m_scr, l_scr,
                      acc_scr,
                      k_ref[0, 0].astype(jnp.float32),
                      v_ref[0, 0].astype(jnp.float32),
                      block_size=block_size, nbt=nbt, scale=scale)


def _paged_int8_kernel(tbl_ref, len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                       kn_ref, vn_ref, o_ref, m_scr, l_scr, acc_scr, *,
                       block_size: int, nbt: int, scale: float):
    """Int8-dequantising variant: the pool blocks arrive as int8 with the
    block's (block_size, KV) f32 scale tile streamed through the SAME
    block-table index map; dequantisation is one broadcast multiply by
    this head's scale column in VMEM, then the shared online-softmax
    body runs unchanged.  The current token's ``kn``/``vn`` stay full
    precision (not yet pooled).
    """
    del tbl_ref
    h = pl.program_id(1)
    _paged_accumulate(q_ref, len_ref, kn_ref, vn_ref, o_ref, m_scr, l_scr,
                      acc_scr,
                      k_ref[0, 0].astype(jnp.float32)
                      * head_column(ks_ref[0], h),
                      v_ref[0, 0].astype(jnp.float32)
                      * head_column(vs_ref[0], h),
                      block_size=block_size, nbt=nbt, scale=scale)


def _paged_call(kernel, extra_specs, q, tables, lengths, *operands,
                name: str, interpret: bool):
    """Shared ``pallas_call`` of the paged decode kernels: grid
    ``(B, KV, NBT)``; ``operands`` are the heads-major page planes (and
    scale tiles, with ``extra_specs`` their block specs) followed by the
    current token's k/v.  ``name`` is the kernel's name in the compiled
    program and in a device trace."""
    B, KV, G, hd = q.shape
    block_size = operands[0].shape[2]
    page_spec = pl.BlockSpec((1, 1, block_size, hd),
                             lambda b, h, j, t, n: (t[b, j], h, 0, 0))
    tok_spec = pl.BlockSpec((1, 1, 1, hd), lambda b, h, j, t, n: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                # tables, lengths
        grid=(B, KV, tables.shape[1]),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, j, t, n: (b, h, 0, 0)),
            *extra_specs(page_spec), tok_spec, tok_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, j, t, n: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
        name=name,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), q, *operands)


def paged_gqa_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     k_new: jax.Array, v_new: jax.Array, tables: jax.Array,
                     lengths: jax.Array, *, interpret: bool = False
                     ) -> jax.Array:
    """q: (B, KV, G, hd) query heads grouped per kv head;
    k_pages/v_pages: (NP, BS, KV, hd) physical block pool;
    k_new/v_new: (B, KV, 1, hd) current token; tables: (B, NBT) int32
    physical block ids; lengths: (B,) int32 valid pool positions.

    Attends over pool positions [0, lengths[b]) plus the explicit
    current token.  Rows with length 0 reduce to softmax over the new
    token alone (out = v_new), so inactive serve rows are well-defined.
    """
    kernel = functools.partial(_paged_kernel, block_size=k_pages.shape[1],
                               nbt=tables.shape[1],
                               scale=1.0 / np.sqrt(q.shape[-1]))
    return _paged_call(kernel, lambda page: (page, page), q, tables, lengths,
                       heads_major(k_pages), heads_major(v_pages),
                       k_new, v_new,
                       # the op name a device trace shows: readers of the
                       # trace find the paged decode attention by it
                       name="paged_gqa_decode", interpret=interpret)


def paged_gqa_decode_int8(q: jax.Array, k_pages: jax.Array,
                          k_scale: jax.Array, v_pages: jax.Array,
                          v_scale: jax.Array, k_new: jax.Array,
                          v_new: jax.Array, tables: jax.Array,
                          lengths: jax.Array, *, interpret: bool = False
                          ) -> jax.Array:
    """``paged_gqa_decode`` over an int8 pool with per-token scales.

    k_pages/v_pages: (NP, BS, KV, hd) int8; k_scale/v_scale:
    (NP, BS, KV, 1) f32 — symmetric per-(token, kv-head) scales written
    alongside each quantised token.  The scale planes ride the SAME
    scalar-prefetched block table as the int8 blocks, so each grid step
    DMAs one (BS, hd) int8 plane plus its block's (BS, KV) scales and
    dequantises in VMEM — no materialised f32 pool anywhere.  q and the
    current token's k_new/v_new stay full precision.
    """
    BS, KV = k_pages.shape[1], k_pages.shape[2]
    kernel = functools.partial(_paged_int8_kernel, block_size=BS,
                               nbt=tables.shape[1],
                               scale=1.0 / np.sqrt(q.shape[-1]))
    scale_spec = pl.BlockSpec((1, BS, KV),
                              lambda b, h, j, t, n: (t[b, j], 0, 0))
    return _paged_call(kernel,
                       lambda page: (page, scale_spec, page, scale_spec),
                       q, tables, lengths,
                       heads_major(k_pages), scale_tiles(k_scale),
                       heads_major(v_pages), scale_tiles(v_scale),
                       k_new, v_new, name="paged_gqa_decode_int8",
                       interpret=interpret)
