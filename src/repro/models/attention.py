"""Reference (pure-jnp) attention paths: train, blockwise prefill, decode.

These are the HOST-target implementations (the "x86 software function" in
Xar-Trek terms).  The ACCEL target swaps in the Pallas kernels from
``repro.kernels`` at MigratableFunction boundaries: ``decode_attention``
and ``paged_decode_attention`` take a ``backend=`` selector ("xla" keeps
the reference math, "pallas" routes the same ABI through the Pallas
decode kernels), so the serve engine can register genuinely different
HOST/ACCEL builds of one step function.

GQA with padded query heads: query heads are padded to a TP-divisible
count ``Hp``; padded heads have zero weights and their kv mapping is
clamped, so they compute attention over zeros and contribute nothing.
KV heads are replicated across TP by default (small), while the KV
*cache* is sharded along the sequence dim.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.models.common import dequantize_int8, quantize_int8

NEG_INF = -1e30


def kv_head_index(num_heads: int, num_kv_heads: int,
                  padded_heads: int) -> np.ndarray | None:
    """Static map: query head -> kv head (padded heads clamp to the last).

    Returns None when the map is the identity (MHA, no padding): gathering
    with an identity index is not free under GSPMD — on the kv-sharded
    cache it lowered to a full cache all-gather (68 GB/chip on the olmoe
    decode_32k cell; see EXPERIMENTS.md §Perf 2).
    """
    group = max(num_heads // num_kv_heads, 1)
    idx = np.minimum(np.arange(padded_heads), num_heads - 1) // group
    idx = np.minimum(idx, num_kv_heads - 1)
    if len(idx) == num_kv_heads and np.array_equal(idx, np.arange(num_kv_heads)):
        return None
    return idx


def plain_attention(q, k, v, *, causal: bool = True,
                    kv_index: np.ndarray | None = None) -> jax.Array:
    """q: (B,S,Hp,hd)  k,v: (B,T,KV,hd) -> (B,S,Hp,hd).  O(S*T) memory."""
    B, S, Hp, hd = q.shape
    T = k.shape[1]
    if kv_index is not None:
        k = k[:, :, kv_index, :]
        v = v[:, :, kv_index, :]
    scale = 1.0 / np.sqrt(hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        qpos = jnp.arange(S)[:, None]
        kpos = jnp.arange(T)[None, :]
        scores = jnp.where(kpos <= qpos + (T - S), scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)


def blockwise_attention(q, k, v, *, causal: bool = True,
                        kv_index: np.ndarray | None = None,
                        block_q: int = 1024, block_k: int = 1024) -> jax.Array:
    """FlashAttention-style online-softmax attention in pure jnp.

    Memory O(block_q * block_k) instead of O(S*T): required for the 32k
    prefill cells.  Iterates only the causally-live (qi, ki) block pairs
    (the full-square version wasted 2.1e14 FLOPs/chip on the qwen
    prefill cell; EXPERIMENTS.md §Perf 3).  Forward-only use (prefill);
    training uses plain_attention at 4k (cheaper to remat).
    """
    B, S, Hp, hd = q.shape
    T = k.shape[1]
    if kv_index is not None:
        k = k[:, :, kv_index, :]
        v = v[:, :, kv_index, :]
    nq, nk = S // block_q, T // block_k
    scale = 1.0 / np.sqrt(hd)
    off = T - S                                     # kv positions ahead of q

    qr = q.transpose(0, 2, 1, 3)                    # (B,H,S,hd)
    kr = k.transpose(0, 2, 1, 3)
    vr = v.transpose(0, 2, 1, 3)

    # static schedule of live block pairs, ki innermost
    pairs = []
    for qi in range(nq):
        hi = min(nk, (qi * block_q + block_q - 1 + off) // block_k + 1) \
            if causal else nk
        for ki in range(hi):
            pairs.append((qi, ki, ki == hi - 1))
    qi_arr = jnp.asarray([p[0] for p in pairs], jnp.int32)
    ki_arr = jnp.asarray([p[1] for p in pairs], jnp.int32)

    m0 = jnp.full((B, Hp, block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hp, block_q, 1), jnp.float32)
    a0 = jnp.zeros((B, Hp, block_q, hd), jnp.float32)
    out0 = jnp.zeros((nq, B, Hp, block_q, hd), q.dtype)

    def step(carry, pk):
        m, l, acc, out = carry
        qi, ki = pk
        reset = (ki == 0)
        m = jnp.where(reset, NEG_INF, m)
        l = jnp.where(reset, 0.0, l)
        acc = jnp.where(reset, 0.0, acc)
        qb = jax.lax.dynamic_slice_in_dim(qr, qi * block_q, block_q, 2)
        kb = jax.lax.dynamic_slice_in_dim(kr, ki * block_k, block_k, 2)
        vb = jax.lax.dynamic_slice_in_dim(vr, ki * block_k, block_k, 2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, kb).astype(jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jnp.arange(block_q)[:, None]
            kpos = ki * block_k + jnp.arange(block_k)[None, :]
            s = jnp.where(kpos <= qpos + off, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(qb.dtype), vb).astype(jnp.float32)
        final = (acc / jnp.maximum(l, 1e-20)).astype(q.dtype)
        # last write per qi slot wins (= this qi's final ki step)
        out = jax.lax.dynamic_update_slice(
            out, final[None], (qi, 0, 0, 0, 0))
        return (m_new, l, acc, out), None

    (_, _, _, out), _ = jax.lax.scan(step, (m0, l0, a0, out0),
                                     (qi_arr, ki_arr))
    # (nq,B,H,bq,hd) -> (B,S,H,hd)
    return out.transpose(1, 0, 3, 2, 4).reshape(B, S, Hp, hd)


def attention(q, k, v, *, causal: bool = True,
              kv_index: np.ndarray | None = None,
              blockwise_threshold: int = 8192) -> jax.Array:
    if q.shape[1] > blockwise_threshold:
        return blockwise_attention(q, k, v, causal=causal, kv_index=kv_index)
    return plain_attention(q, k, v, causal=causal, kv_index=kv_index)


# ------------------------------------------------- ACCEL (Pallas) path

@jax.custom_vjp
def flash_attention_hybrid(q, k, v):
    """Causal flash attention: Pallas kernel forward, reference backward.

    The forward streams q/k/v blocks through VMEM (no S x S score
    materialisation); the backward recomputes scores once via the
    reference path (a dedicated bwd kernel is the next step and would
    remove that too).  q/k/v: (B,S,H,hd) with kv already head-expanded.
    """
    from repro.kernels import ops as kernel_ops
    return kernel_ops.flash_attention(q, k, v, causal=True)


def _flash_fwd(q, k, v):
    return flash_attention_hybrid(q, k, v), (q, k, v)


def _flash_bwd(res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda a, b, c: plain_attention(a, b, c, causal=True), q, k, v)
    return vjp(g)


flash_attention_hybrid.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_sharded(q, k, v, mesh, *,
                            kv_index: np.ndarray | None = None):
    """shard_map'd flash attention: batch over (pod, data), heads over
    model; per shard the Pallas kernel runs on its local blocks."""
    if kv_index is not None:
        k = k[:, :, kv_index, :]
        v = v[:, :, kv_index, :]
    if mesh is None:
        return flash_attention_hybrid(q, k, v)
    from jax.sharding import PartitionSpec as P
    from repro.parallel.mesh import MODEL_AXIS, batch_axes
    bdims = batch_axes(mesh)
    B = q.shape[0]
    dp = 1
    for a in bdims:
        dp *= mesh.shape[a]
    bspec = bdims if (bdims and B % dp == 0) else None
    spec = P(bspec, None, MODEL_AXIS, None)
    f = jax.shard_map(flash_attention_hybrid, mesh=mesh,
                      in_specs=(spec, spec, spec), out_specs=spec,
                      check_vma=False)
    return f(q, k, v)


# ------------------------------------------------------------------ cache

def init_kv_cache(num_layers: int, batch: int, max_seq: int,
                  num_kv_heads: int, head_dim: int, dtype: str) -> dict:
    shape = (num_layers, batch, max_seq, num_kv_heads, head_dim)
    if dtype == "int8":
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1] + (1,), jnp.float32),
            "v_scale": jnp.zeros(shape[:-1] + (1,), jnp.float32),
        }
    dt = jnp.dtype(dtype)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


LANE_WIDTH = 128     # TPU MXU/VPU lane width the Pallas kernels tile to


def init_paged_kv_cache(num_layers: int, num_blocks: int, block_size: int,
                        num_kv_heads: int, head_dim: int, dtype: str,
                        lane_align: bool | None = None) -> dict:
    """Block-pool KV cache for paged continuous batching.

    Layout (L, num_blocks, block_size, KV, hd): physical blocks replace
    the dense (batch, max_seq) plane; a per-request block table maps
    logical position p to (table[p // block_size], p % block_size).
    ``num_blocks`` counts PHYSICAL blocks, i.e. the pool's usable blocks
    plus the reserved junk block 0 (see serve.batch.BlockPool).

    ``lane_align`` pads ``head_dim`` up to the TPU lane width (128) *at
    allocation*, so the ACCEL paged kernel never has to lane-pad (=
    copy) the whole pool per decode call — writers zero-pad the
    per-token KV instead (cheap) and readers slice the real ``head_dim``
    back out.  ``None`` (default) aligns exactly when the Pallas
    kernels would compile natively (``not interpret``), and keeps the
    historical unpadded layout in interpret mode so CI behaviour — and
    CI memory — is unchanged.

    ``dtype="int8"`` stores the pool quantised with parallel f32 scale
    pools (``k_scale``/``v_scale``, same block structure, trailing dim 1)
    at per-(token, kv-head) granularity: every write quantises its own
    token independently (symmetric, round-to-nearest-even over
    ``head_dim``), so decode appends never re-scale a block's existing
    tokens and a COW fork copies scales the same way it copies blocks.
    At equal bytes an int8+scales pool holds ``~4*hd/(hd+4)`` as many
    blocks as f32 (~3.5x at hd=32, ~2x vs bf16); see
    ``paged_kv_block_bytes``.
    """
    if lane_align is None:
        from repro.kernels.ops import _interpret
        lane_align = not _interpret(None)
    hd_alloc = (head_dim + (-head_dim) % LANE_WIDTH if lane_align
                else head_dim)
    shape = (num_layers, num_blocks, block_size, num_kv_heads, hd_alloc)
    if dtype == "int8":
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1] + (1,), jnp.float32),
            "v_scale": jnp.zeros(shape[:-1] + (1,), jnp.float32),
        }
    dt = jnp.dtype(dtype)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def paged_kv_block_bytes(block_size: int, num_kv_heads: int, head_dim: int,
                         dtype: str, lane_align: bool = False) -> int:
    """Bytes one physical KV block occupies (k + v + any scale pools).

    The equal-KV-bytes currency for capacity planning: at a fixed byte
    budget an int8 pool allocates ``budget // paged_kv_block_bytes``
    blocks — ~3.5x the f32 count at hd=32 (int8 pays 1 byte/element
    plus 4 bytes per (token, head) for the scale) — which is what
    ``PagedSlotManager.can_admit`` sees as extra admission headroom.
    """
    hd = (head_dim + (-head_dim) % LANE_WIDTH if lane_align else head_dim)
    per_pos = 2 * num_kv_heads * hd          # k + v elements per position
    if dtype == "int8":
        return block_size * (per_pos + 2 * num_kv_heads * 4)
    return block_size * per_pos * jnp.dtype(dtype).itemsize


def cache_specs(rules, int8: bool) -> dict:
    """PartitionSpecs matching init_kv_cache layout."""
    s = rules.spec("layers", "batch", "cache_seq", "kv_heads", "head_dim")
    out = {"k": s, "v": s}
    if int8:
        sc = rules.spec("layers", "batch", "cache_seq", "kv_heads", None)
        out.update({"k_scale": sc, "v_scale": sc})
    return out


def update_cache_layer(cache: dict, layer: int, index: jax.Array,
                       k_new: jax.Array, v_new: jax.Array) -> dict:
    """Write (B, 1, KV, hd) new keys/values at ``index`` of layer ``layer``."""
    int8 = cache["k"].dtype == jnp.int8
    upd = dict(cache)
    if int8:
        kq, ks = quantize_int8(k_new, axis=-1)
        vq, vs = quantize_int8(v_new, axis=-1)
        for name, val in (("k", kq), ("v", vq), ("k_scale", ks), ("v_scale", vs)):
            upd[name] = jax.lax.dynamic_update_slice(
                upd[name], val[None].astype(upd[name].dtype),
                (layer, 0, index, 0, 0))
    else:
        for name, val in (("k", k_new), ("v", v_new)):
            upd[name] = jax.lax.dynamic_update_slice(
                upd[name], val[None].astype(upd[name].dtype),
                (layer, 0, index, 0, 0))
    return upd


def read_cache_layer(cache: dict, layer: int, dtype=jnp.bfloat16):
    k, v = cache["k"][layer], cache["v"][layer]
    if k.dtype == jnp.int8:
        k = dequantize_int8(k, cache["k_scale"][layer], dtype)
        v = dequantize_int8(v, cache["v_scale"][layer], dtype)
    return k, v


def _static_kv_index(kv_index) -> tuple | None:
    """np.ndarray -> hashable tuple for the jitted kernel wrappers."""
    if kv_index is None:
        return None
    return tuple(int(i) for i in np.asarray(kv_index))


def decode_attention(q, k_cache, v_cache, index: jax.Array,
                     kv_index: np.ndarray | None = None,
                     k_new=None, v_new=None, backend: str = "xla"
                     ) -> jax.Array:
    """Single-token attention over a (possibly seq-sharded) cache.

    q: (B,1,Hp,hd); k_cache/v_cache: (B,Smax,KV,hd).  ``index`` is a
    scalar shared position, or (B,1,1,1) ragged per-row positions
    (continuous batching) — both broadcast against the (…,Smax) masks.

    With ``k_new/v_new`` (B,1,KV,hd) given, attends over cache[0,index)
    plus the explicit current token — so callers can READ the old cache
    and WRITE the new entry independently.  (The write-then-read pattern
    defeats XLA's in-place aliasing of the scan-carried cache: the
    baseline olmoe decode cell copied the full 1 GB cache stack per layer
    — 103 GB/chip/step of pure copy traffic; EXPERIMENTS.md §Perf 2.)
    Without k_new, attends over [0, index] (cache already updated).

    ``backend="pallas"`` runs the same computation through the Pallas
    decode kernels (the ACCEL variant); "xla" is the reference below.
    """
    if backend == "pallas":
        from repro.kernels import ops as kernel_ops
        kvt = _static_kv_index(kv_index)
        if k_new is None:
            return kernel_ops.gqa_decode(q, k_cache, v_cache, index,
                                         kv_index=kvt)
        return kernel_ops.gqa_decode_ragged(q, k_cache, v_cache, index,
                                            k_new, v_new, kv_index=kvt)
    B, _, Hp, hd = q.shape
    Smax = k_cache.shape[1]
    if kv_index is not None:
        k_cache = k_cache[:, :, kv_index, :]
        v_cache = v_cache[:, :, kv_index, :]
        if k_new is not None:
            k_new = k_new[:, :, kv_index, :]
            v_new = v_new[:, :, kv_index, :]
    scale = 1.0 / np.sqrt(hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache).astype(jnp.float32) * scale
    positions = jnp.arange(Smax)[None, None, None, :]
    if k_new is None:
        mask = positions <= index
        scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v_cache)

    # explicit current-token term (cache holds only positions < index)
    mask = positions < index
    scores = jnp.where(mask, scores, NEG_INF)
    s_cur = (jnp.einsum("bqhd,bkhd->bhqk", q, k_new)
             .astype(jnp.float32) * scale)            # (B,Hp,1,1)
    m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), s_cur)
    p = jnp.exp(scores - m)
    p_cur = jnp.exp(s_cur - m)
    denom = jnp.sum(p, axis=-1, keepdims=True) + p_cur
    out = jnp.einsum("bhqk,bkhd->bqhd", (p / denom).astype(q.dtype), v_cache)
    out = out + jnp.einsum("bhqk,bkhd->bqhd",
                           (p_cur / denom).astype(q.dtype), v_new)
    return out


def paged_decode_attention(q, k_pages, v_pages, table, index: jax.Array,
                           k_new, v_new,
                           kv_index: np.ndarray | None = None,
                           backend: str = "xla",
                           k_scale=None, v_scale=None) -> jax.Array:
    """Single-token attention over one layer of a paged (block-pool) cache.

    q: (B,1,Hp,hd); k_pages/v_pages: (NP,BS,KV,hd) physical blocks;
    table: (B,NBT) int32 block ids (logical block j of row b lives at
    ``table[b, j]``); index: (B,) int32 per-row write positions.  The
    pool contributes positions [0, index) and the current token's
    ``k_new/v_new`` (B,1,KV,hd) is folded in explicitly
    (write-then-attend, as in ``decode_attention``).

    With ``k_scale``/``v_scale`` (NP,BS,KV,1) f32 given the pool is
    int8 (see ``init_paged_kv_cache``): HOST dequantises the gathered
    rows before attending, ACCEL streams blocks + scales through the
    int8 kernel and dequantises in VMEM — same math, so greedy tokens
    agree across targets within the documented int8 tolerance.

    backend="xla" gathers the row's blocks into logical order and reuses
    ``decode_attention`` (the HOST reference — one materialised
    (B, NBT*BS, KV, hd) cache per call); backend="pallas" streams the
    blocks inside the paged decode kernel with no materialised gather
    (the ACCEL variant).
    """
    if backend == "pallas":
        from repro.kernels import ops as kernel_ops
        if k_scale is not None:
            return kernel_ops.paged_gqa_decode_int8(
                q, k_pages, k_scale, v_pages, v_scale, k_new, v_new,
                table, index, kv_index=_static_kv_index(kv_index))
        return kernel_ops.paged_gqa_decode(
            q, k_pages, v_pages, k_new, v_new, table, index,
            kv_index=_static_kv_index(kv_index))
    B = q.shape[0]
    hd = q.shape[-1]
    NBT = table.shape[1]
    BS = k_pages.shape[1]
    rows_k = jnp.take(k_pages, table, axis=0)         # (B, NBT, BS, KV, hdp)
    rows_v = jnp.take(v_pages, table, axis=0)
    if k_scale is not None:
        # int8 pool: gather the per-token scales the same way and
        # dequantise only the (small) gathered rows, never the pool
        rows_k = (rows_k.astype(jnp.float32)
                  * jnp.take(k_scale, table, axis=0)).astype(q.dtype)
        rows_v = (rows_v.astype(jnp.float32)
                  * jnp.take(v_scale, table, axis=0)).astype(q.dtype)
    if rows_k.shape[-1] != hd:
        # lane-aligned pool (hd padded to 128 at allocation): the padded
        # tail is all-zero; slice AFTER the gather so only the (small)
        # gathered rows are touched, never the whole pool
        rows_k = rows_k[..., :hd]
        rows_v = rows_v[..., :hd]
    kc = rows_k.reshape(B, NBT * BS, *rows_k.shape[3:])
    vc = rows_v.reshape(B, NBT * BS, *rows_v.shape[3:])
    return decode_attention(q, kc, vc, index[:, None, None, None],
                            kv_index=kv_index, k_new=k_new, v_new=v_new)


def paged_prefill_attention(q, k_pages, v_pages, table, offset, length,
                            k_new, v_new,
                            kv_index: np.ndarray | None = None,
                            backend: str = "xla",
                            k_scale=None, v_scale=None) -> jax.Array:
    """Chunked-prefill attention: a multi-token chunk extends a prefix
    already resident in a paged cache (prefix caching's partial prefill).

    q: (B,W,Hp,hd) chunk queries at ABSOLUTE positions ``offset + j``;
    k_pages/v_pages: (NP,BS,KV,hd) one layer of the block pool; table:
    (B,NBT) int32 block ids; offset/length: (B,) int32.  The pool
    contributes logical positions [0, offset) — the cached prefix,
    written by an earlier request's prefill — and the chunk supplies
    positions [offset, length) causally through ``k_new/v_new``
    (B,W,KV,hd).  Chunk columns at or past ``length - offset`` are
    bucket padding: masked for every query, like the pool's junk-block
    columns past ``offset`` (their exp-underflowed scores are exact
    0.0, so padding width never changes the math — the same argument
    as the bucketed dense prefill).  Fully-masked PADDING query rows
    come out as garbage-but-finite values; callers never read them.

    With ``k_scale``/``v_scale`` (NP,BS,KV,1) given the pool is int8:
    the gathered context rows are dequantised (scale multiply, f32)
    before the chunk attends over them — the chunk's own ``k_new/v_new``
    stay full precision.

    backend="xla" gathers the row's blocks and attends over the
    materialised context (the HOST reference below); backend="pallas"
    streams pool blocks through the chunk-prefill kernel
    (``kernels.gqa_prefill.paged_gqa_prefill``) masked to [0, offset)
    with the chunk's causal self-attention folded in-kernel — chunked
    prefill is a genuinely different ACCEL build, like decode.
    """
    if backend == "pallas":
        from repro.kernels import ops as kernel_ops
        kvt = _static_kv_index(kv_index)
        if k_scale is not None:
            return kernel_ops.paged_gqa_prefill_int8(
                q, k_pages, k_scale, v_pages, v_scale, k_new, v_new,
                table, offset, length, kv_index=kvt)
        return kernel_ops.paged_gqa_prefill(
            q, k_pages, v_pages, k_new, v_new, table, offset, length,
            kv_index=kvt)
    B, W, Hp, hd = q.shape
    NBT = table.shape[1]
    BS = k_pages.shape[1]
    rows_k = jnp.take(k_pages, table, axis=0)         # (B, NBT, BS, KV, hdp)
    rows_v = jnp.take(v_pages, table, axis=0)
    if k_scale is not None:
        rows_k = (rows_k.astype(jnp.float32)
                  * jnp.take(k_scale, table, axis=0)).astype(q.dtype)
        rows_v = (rows_v.astype(jnp.float32)
                  * jnp.take(v_scale, table, axis=0)).astype(q.dtype)
    if rows_k.shape[-1] != hd:
        rows_k = rows_k[..., :hd]                     # lane-aligned pool
        rows_v = rows_v[..., :hd]
    T = NBT * BS
    kc = rows_k.reshape(B, T, *rows_k.shape[3:]).astype(q.dtype)
    vc = rows_v.reshape(B, T, *rows_v.shape[3:]).astype(q.dtype)
    if kv_index is not None:
        kc = kc[:, :, kv_index, :]
        vc = vc[:, :, kv_index, :]
        k_new = k_new[:, :, kv_index, :]
        v_new = v_new[:, :, kv_index, :]
    k_full = jnp.concatenate([kc, k_new.astype(q.dtype)], axis=1)
    v_full = jnp.concatenate([vc, v_new.astype(q.dtype)], axis=1)
    scale = 1.0 / np.sqrt(hd)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q, k_full)
              .astype(jnp.float32) * scale)           # (B,Hp,W,T+W)
    ctx_valid = jnp.arange(T)[None, :] < offset[:, None]        # (B,T)
    qi = jnp.arange(W)[:, None]
    kj = jnp.arange(W)[None, :]
    n_real = (length - offset)[:, None, None]                   # (B,1,1)
    self_valid = (kj <= qi)[None] & (kj[None] < n_real)         # (B,W,W)
    mask = jnp.concatenate(
        [jnp.broadcast_to(ctx_valid[:, None, :], (B, W, T)), self_valid],
        axis=-1)[:, None]                                       # (B,1,W,T+W)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v_full)


def paged_verify_attention(q, k_pages, v_pages, table, offset, length,
                           k_new, v_new,
                           kv_index: np.ndarray | None = None,
                           backend: str = "xla",
                           k_scale=None, v_scale=None) -> jax.Array:
    """Speculative-decode VERIFY attention over a paged KV cache.

    The target model scores a slot's k drafted tokens in one batched
    step.  Mathematically this is ``paged_prefill_attention`` exactly:
    the "chunk" is the drafted span ``[offset, length)`` (W = draft
    width, columns past ``length - offset`` are per-row padding for
    shrunk drafts), and the pool contributes the committed prefix
    ``[0, offset)``.  The HOST path therefore delegates to the prefill
    reference verbatim; the ACCEL path routes through the verify-named
    kernel wrappers (``kernels.ops.paged_gqa_verify`` / ``_int8``) so
    the serve engine registers verify as a DISTINCT binary — the
    Xar-Trek runtime's migration log and ``summary()`` accounting then
    see draft and verify calls independently per target.
    """
    if backend == "pallas":
        from repro.kernels import ops as kernel_ops
        kvt = _static_kv_index(kv_index)
        if k_scale is not None:
            return kernel_ops.paged_gqa_verify_int8(
                q, k_pages, k_scale, v_pages, v_scale, k_new, v_new,
                table, offset, length, kv_index=kvt)
        return kernel_ops.paged_gqa_verify(
            q, k_pages, v_pages, k_new, v_new, table, offset, length,
            kv_index=kvt)
    return paged_prefill_attention(q, k_pages, v_pages, table, offset,
                                   length, k_new, v_new, kv_index=kv_index,
                                   backend="xla", k_scale=k_scale,
                                   v_scale=v_scale)
