"""Single-token decode attention over a long KV cache — Pallas TPU kernels.

Flash-decoding style: ``gqa_decode`` (grid ``(batch*heads, kv_blocks)``)
streams a dense cache through VMEM with online-softmax accumulators in
scratch (one q-row per program), masked at the live length.

``paged_gqa_decode`` is the block-table-aware variant for the paged
(vLLM-style) KV pool.  Its grid is ``(B,)``: one step per row covers
every kv head.  The block table and per-row live lengths ride in as
scalar-prefetch operands; the pool stays in HBM (``pl.ANY``) and the
kernel copies the row's ``n = cdiv(length, BS)`` live blocks itself, in
groups of ``group_blocks(BS, NBT)`` (about 256 positions): one
contiguous DMA per block and pool into a double-buffered VMEM scratch,
group g+1's copies started before group g is computed.  A table entry
at or past ``n`` is never read, so a decode step costs the live
context, not the reserved table, and the per-row gathered cache is
never materialised.  Per group, each kv head's ``G`` query rows are
scored against the group's keys, masked at the live length, and folded
into per-head online-softmax scratch in f32.  The current token's K/V
is passed explicitly and folded in last (write-then-attend: the pool
contributes positions ``< length`` only).

``paged_gqa_decode_int8`` runs the same body over an int8 pool: each
live block's scales are copied beside its int8 planes (one lane-padded
row per block, ``scale_rows``) and dequantised in VMEM — the pool is
never materialised at full precision.  The two kernels differ only in
that dequantise step (``_planes`` / ``_planes_int8``).

TPU tiling: the pool's ``(NP, BS, KV, hd)`` ABI is seen as
``(NP, KV, BS, hd)`` (``heads_major``), so a block of every kv head is
one contiguous ``(KV, BS, hd)`` copy; a copy's minor dim must fill
whole 128-lane tiles, hence the scale rows' padding.

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


def scale_rows(scale: jax.Array) -> jax.Array:
    """(NP, BS, KV, 1) scale pool -> (NP, 1, W) f32: each block's scales
    token-major in one row (token t, kv head h at lane ``t * KV + h``),
    zero-padded to whole 128-lane tiles, the least a block's copy may
    move (a DMA's minor dim must fill whole lane tiles)."""
    NP, BS, KV, _ = scale.shape
    rows = scale.astype(jnp.float32).reshape(NP, 1, BS * KV)
    return jnp.pad(rows, ((0, 0), (0, 0), (0, -(BS * KV) % 128)))


def group_blocks(block_size: int, nbt: int) -> int:
    """Table blocks per DMA group: about 256 pool positions, never more
    blocks than the table holds."""
    return min(nbt, max(1, 256 // block_size))


def _planes(bufs, slot, h):
    """Kv head ``h``'s K and V rows of one buffer slot, as f32
    ``(blocks * BS, hd)`` — a bf16 or f32 pool is used verbatim."""
    k, v = (buf[slot, :, h].astype(jnp.float32) for buf in bufs)
    return k.reshape(-1, k.shape[-1]), v.reshape(-1, v.shape[-1])


def _planes_int8(bufs, slot, h):
    """As ``_planes`` for an int8 pool: each row times its scale, kv
    head ``h``'s lane of its block's scale row, selected in VMEM."""
    kb, ksb, vb, vsb = bufs
    n_blocks, kv, block_size, _ = kb.shape[1:]

    def dequantise(buf, sbuf):
        x = buf[slot, :, h].astype(jnp.float32)       # (blocks, BS, hd)
        rows = sbuf[slot]                             # (blocks, 1, W)
        shape = (n_blocks, block_size, rows.shape[-1])
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        token = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        col = jnp.sum(jnp.where(lane == token * kv + h, rows, 0.0), axis=2,
                      keepdims=True)                  # (blocks, BS, 1)
        return (x * col).reshape(-1, x.shape[-1])

    return dequantise(kb, ksb), dequantise(vb, vsb)


def _paged_kernel(tbl_ref, len_ref, q_ref, kn_ref, vn_ref, *refs, planes,
                  n_pools: int, block_size: int, nbt: int, per_group: int,
                  scale: float):
    """One grid step per row: every kv head over the row's live blocks.

    ``refs`` are the ``n_pools`` heads-major pools left in HBM, the
    output block, a double-buffered VMEM copy of ``per_group`` blocks
    per pool, the DMA semaphores and the per-head online-softmax
    scratch.  The row's ``n = cdiv(length, BS)`` live blocks are walked
    in groups: group g+1's copies (one contiguous DMA per block and
    pool) start before group g is computed, and no table entry at or
    past ``n`` is ever read.
    """
    pools, o_ref = refs[:n_pools], refs[n_pools]
    bufs = refs[n_pools + 1:2 * n_pools + 1]
    sems, m_scr, l_scr, acc_scr = refs[2 * n_pools + 1:]
    b = pl.program_id(0)
    live = len_ref[b]                                 # pool valid on [0, live)
    n = jnp.minimum(pl.cdiv(live, block_size), nbt)   # live blocks
    n_groups = pl.cdiv(n, per_group)
    width = per_group * block_size

    def copy_group(g, slot, start: bool):
        for i in range(per_group):
            @pl.when(g * per_group + i < n)
            def _copy():
                blk = tbl_ref[b, g * per_group + i]
                for p, (pool, buf) in enumerate(zip(pools, bufs)):
                    dma = pltpu.make_async_copy(pool.at[blk], buf.at[slot, i],
                                                sems.at[p, slot])
                    if start:
                        dma.start()
                    else:
                        dma.wait()

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(n > 0)
    def _first():
        copy_group(0, 0, start=True)

    def group(g, carry):
        slot = jax.lax.rem(g, 2)

        @pl.when(g + 1 < n_groups)
        def _next():
            copy_group(g + 1, 1 - slot, start=True)

        copy_group(g, slot, start=False)
        pos = g * width
        key_live = pos + jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        row_live = pos + jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)
        for h in range(q_ref.shape[1]):
            k, v = planes(bufs, slot, h)
            q = q_ref[0, h].astype(jnp.float32)       # (G, hd)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(key_live < live, s * scale, NEG_INF)
            # slots past the live length hold stale VMEM: p is 0 there,
            # and 0 * NaN would still leak through p @ v
            v = jnp.where(row_live < live, v, 0.0)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)

    # fold the current token (position ``live``, not yet in the pool):
    # a row of length 0 reduces to softmax over it alone, out = v_new
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(jnp.float32)
        kn = kn_ref[0, h].astype(jnp.float32)         # (1, hd)
        vn = vn_ref[0, h].astype(jnp.float32)
        s_cur = jax.lax.dot_general(q, kn, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
        m_prev = m_scr[h]
        m_fin = jnp.maximum(m_prev, s_cur)
        corr = jnp.exp(m_prev - m_fin)
        p_cur = jnp.exp(s_cur - m_fin)
        l_fin = l_scr[h] * corr + p_cur
        acc = acc_scr[h] * corr + p_cur * vn
        o_ref[0, h] = (acc / jnp.maximum(l_fin, 1e-20)).astype(o_ref.dtype)


def _paged_call(planes, q, tables, lengths, pools, k_new, v_new, *,
                name: str, interpret):
    """Shared ``pallas_call`` of the paged decode kernels: grid ``(B,)``;
    ``pools`` are the heads-major page planes (and scale rows), left in
    HBM for the kernel's own DMAs.  ``name`` is the kernel's name in the
    compiled program and in a device trace."""
    B, KV, G, hd = q.shape
    block_size, nbt = pools[0].shape[2], tables.shape[1]
    per_group = group_blocks(block_size, nbt)
    kernel = functools.partial(
        _paged_kernel, planes=planes, n_pools=len(pools),
        block_size=block_size, nbt=nbt, per_group=per_group,
        scale=1.0 / np.sqrt(hd))
    row = lambda b, t, n: (b, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                # tables, lengths
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), row),
            pl.BlockSpec((1, KV, 1, hd), row),
            pl.BlockSpec((1, KV, 1, hd), row),
            *[pl.BlockSpec(memory_space=pl.ANY) for _ in pools],
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd), row),
        scratch_shapes=[
            *[pltpu.VMEM((2, per_group, *p.shape[1:]), p.dtype)
              for p in pools],
            pltpu.SemaphoreType.DMA((len(pools), 2)),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=name,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), q, k_new, v_new,
      *pools)


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
    Table entries at or past ``cdiv(lengths[b], BS)`` are never read.
    """
    # the op name a device trace shows: readers of the trace find the
    # paged decode attention by it
    return _paged_call(_planes, q, tables, lengths,
                       (heads_major(k_pages), heads_major(v_pages)),
                       k_new, v_new, name="paged_gqa_decode",
                       interpret=interpret)


def paged_gqa_decode_int8(q: jax.Array, k_pages: jax.Array,
                          k_scale: jax.Array, v_pages: jax.Array,
                          v_scale: jax.Array, k_new: jax.Array,
                          v_new: jax.Array, tables: jax.Array,
                          lengths: jax.Array, *, interpret: bool = False
                          ) -> jax.Array:
    """``paged_gqa_decode`` over an int8 pool with per-token scales.

    k_pages/v_pages: (NP, BS, KV, hd) int8; k_scale/v_scale:
    (NP, BS, KV, 1) f32 — symmetric per-(token, kv-head) scales written
    alongside each quantised token.  Each live block's scales are
    copied beside its int8 planes, as one row (``scale_rows``), and the
    kernel dequantises in VMEM — no materialised f32 pool anywhere.  q and the current
    token's k_new/v_new stay full precision.
    """
    return _paged_call(_planes_int8, q, tables, lengths,
                       (heads_major(k_pages), scale_rows(k_scale),
                        heads_major(v_pages), scale_rows(v_scale)),
                       k_new, v_new, name="paged_gqa_decode_int8",
                       interpret=interpret)
