"""Paged (block-table-aware) Pallas decode kernel vs the jnp oracles.

The kernel streams a row's physical KV blocks in logical order via
scalar-prefetched block tables — these tests pin its math to
``ref.decode_attention_ref`` (assembling the equivalent dense cache by
hand) and to the HOST gather-then-attend path across GQA ratios, head
dims, ragged live-lengths and block sizes, and show that it never reads
a table entry past a row's live blocks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.gqa_decode import paged_gqa_decode as paged_raw
from repro.models.attention import (
    decode_attention, kv_head_index, paged_decode_attention,
)
from repro.models.common import quantize_int8


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


def _pool_problem(seed, B, KV, hd, NP, BS, NBT, lengths):
    """Random pool + per-row tables of DISTINCT physical blocks (as the
    serve engine allocates), plus the current token's K/V."""
    rng = np.random.RandomState(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    kp = _rand(ks[0], (NP, BS, KV, hd))
    vp = _rand(ks[1], (NP, BS, KV, hd))
    tables = np.stack([rng.permutation(NP)[:NBT] for _ in range(B)])
    kn = _rand(ks[2], (B, 1, KV, hd))
    vn = _rand(ks[3], (B, 1, KV, hd))
    idx = jnp.asarray(lengths, jnp.int32)
    return kp, vp, jnp.asarray(tables, jnp.int32), kn, vn, idx


def _dense_equivalent(q, kp, vp, tables, idx, kn, vn, kv_index):
    """Assemble the dense per-row cache the paged kernel implies (gather
    blocks in logical order, write the new token at ``idx``) and run
    ``ref.decode_attention_ref`` per row over [0, idx] inclusive."""
    B, _, Hp, hd = q.shape
    NP, BS, KV, _ = kp.shape
    NBT = tables.shape[1]
    S = NBT * BS
    kc = np.asarray(kp)[np.asarray(tables)].reshape(B, S, KV, hd).copy()
    vc = np.asarray(vp)[np.asarray(tables)].reshape(B, S, KV, hd).copy()
    for b in range(B):
        kc[b, int(idx[b])] = np.asarray(kn)[b, 0]
        vc[b, int(idx[b])] = np.asarray(vn)[b, 0]
    kvmap = (np.arange(Hp) if kv_index is None else np.asarray(kv_index))
    out = np.zeros((B, 1, Hp, hd), np.float32)
    for b in range(B):
        qf = np.asarray(q)[b].transpose(1, 0, 2)          # (Hp, 1, hd)
        kf = kc[b].transpose(1, 0, 2)[kvmap]              # (Hp, S, hd)
        vf = vc[b].transpose(1, 0, 2)[kvmap]
        row = ref.decode_attention_ref(jnp.asarray(qf), jnp.asarray(kf),
                                       jnp.asarray(vf), jnp.int32(idx[b]))
        out[b] = np.asarray(row).transpose(1, 0, 2)
    return out


@pytest.mark.parametrize("Hp,KV,hd,BS,NBT,lengths", [
    (4, 2, 32, 8, 3, (0, 7, 23)),       # GQA 2:1, zero-length row
    (4, 4, 32, 8, 2, (3, 15, 10)),      # MHA (identity map)
    (3, 1, 64, 16, 2, (0, 31, 17)),     # odd heads onto one kv head
    (8, 2, 16, 4, 4, (15, 1, 8)),       # GQA 4:1, tiny blocks
    (5, 2, 32, 8, 3, (23, 11, 2)),      # non-uniform groups (3 + 2)
])
def test_paged_kernel_matches_decode_attention_ref(Hp, KV, hd, BS, NBT,
                                                   lengths):
    B, NP = len(lengths), NBT * len(lengths) + 1
    kv_idx = None if Hp == KV else kv_head_index(Hp, KV, Hp)
    q = _rand(jax.random.PRNGKey(42), (B, 1, Hp, hd))
    kp, vp, tables, kn, vn, idx = _pool_problem(7, B, KV, hd, NP, BS, NBT,
                                                lengths)
    got = ops.paged_gqa_decode(
        q, kp, vp, kn, vn, tables, idx,
        kv_index=None if kv_idx is None else tuple(int(i) for i in kv_idx))
    want = _dense_equivalent(q, kp, vp, tables, idx, kn, vn, kv_idx)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


def test_paged_raw_kernel_matches_oracle():
    """Raw (already-grouped) kernel vs the pure-jnp paged oracle."""
    B, KV, G, hd, NP, BS, NBT = 3, 2, 3, 32, 7, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = _rand(ks[0], (B, KV, G, hd))
    kp = _rand(ks[1], (NP, BS, KV, hd))
    vp = _rand(ks[2], (NP, BS, KV, hd))
    kn = _rand(ks[3], (B, KV, 1, hd))
    vn = _rand(ks[4], (B, KV, 1, hd))
    rng = np.random.RandomState(0)
    tables = jnp.asarray(rng.randint(0, NP, size=(B, NBT)), jnp.int32)
    idx = jnp.asarray([0, 13, 30], jnp.int32)
    got = paged_raw(q, kp, vp, kn, vn, tables, idx, interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, kn, vn, tables, idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block_k", [8, 16, 64])
def test_gqa_decode_ragged_matches_xla_decode_attention(block_k):
    """Dense-cache ragged decode through the paged kernel (identity block
    table view) vs the XLA reference with the explicit-new-token path."""
    B, Smax, Hp, KV, hd = 3, 64, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    q = _rand(ks[0], (B, 1, Hp, hd))
    kc = _rand(ks[1], (B, Smax, KV, hd))
    vc = _rand(ks[2], (B, Smax, KV, hd))
    kn = _rand(ks[3], (B, 1, KV, hd))
    vn = _rand(ks[4], (B, 1, KV, hd))
    idx = jnp.asarray([0, 29, 63], jnp.int32)
    kv_idx = kv_head_index(Hp, KV, Hp)
    got = ops.gqa_decode_ragged(q, kc, vc, idx, kn, vn,
                                kv_index=tuple(int(i) for i in kv_idx),
                                block_k=block_k)
    want = decode_attention(q, kc, vc, idx[:, None, None, None],
                            kv_index=kv_idx, k_new=kn, v_new=vn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_decode_attention_backends_agree():
    """models.attention.paged_decode_attention: pallas backend (in-kernel
    block streaming) vs xla backend (materialised gather)."""
    B, Hp, KV, hd, NP, BS, NBT = 3, 4, 2, 32, 9, 8, 3
    kv_idx = kv_head_index(Hp, KV, Hp)
    q = _rand(jax.random.PRNGKey(5), (B, 1, Hp, hd))
    kp, vp, tables, kn, vn, idx = _pool_problem(9, B, KV, hd, NP, BS, NBT,
                                                (0, 7, 23))
    outs = {be: paged_decode_attention(q, kp, vp, tables, idx, kn, vn,
                                       kv_index=kv_idx, backend=be)
            for be in ("xla", "pallas")}
    np.testing.assert_allclose(np.asarray(outs["pallas"]),
                               np.asarray(outs["xla"]),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("index", [jnp.int32(41),
                                   jnp.asarray([3, 57], jnp.int32)])
def test_no_knew_dense_decode_backends_agree(index):
    """decode_attention backend="pallas" without k_new (the synchronous
    engine's attend-over-[0,index] shape), scalar and ragged index."""
    B, Smax, Hp, hd = 2, 64, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = _rand(ks[0], (B, 1, Hp, hd))
    kc = _rand(ks[1], (B, Smax, Hp, hd))
    vc = _rand(ks[2], (B, Smax, Hp, hd))
    xla_index = index[:, None, None, None] if index.ndim else index
    got = decode_attention(q, kc, vc, index, backend="pallas")
    want = decode_attention(q, kc, vc, xla_index, backend="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_interpret_env_override_reaches_dispatch(monkeypatch):
    """REPRO_PALLAS_INTERPRET must be resolved per call, outside the jit
    cache: flipping it after a cached trace still takes effect (here:
    forcing native lowering on CPU fails loudly instead of silently
    reusing the interpret-mode executable)."""
    x = jnp.ones((8, 64))
    w = jnp.ones((64,))
    monkeypatch.setenv(ops.INTERPRET_ENV, "1")
    ops.rmsnorm(x, w)                       # traced + cached (interpret)
    monkeypatch.setenv(ops.INTERPRET_ENV, "0")
    with pytest.raises(Exception):          # no TPU: native lowering dies
        jax.block_until_ready(ops.rmsnorm(x, w))
    monkeypatch.delenv(ops.INTERPRET_ENV)
    ops.rmsnorm(x, w)                       # auto default restored


def test_junk_block_and_zero_length_rows_are_well_defined():
    """Length-0 rows (inactive serve slots: all-zero table into the junk
    block) must reduce to softmax over the new token alone — no NaNs, no
    reads of junk content."""
    B, KV, G, hd, NP, BS, NBT = 2, 1, 2, 16, 4, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = _rand(ks[0], (B, KV, G, hd))
    # poison the junk block with huge values: masked positions must not
    # leak them into the output
    kp = jnp.full((NP, BS, KV, hd), 1e4, jnp.float32)
    vp = jnp.full((NP, BS, KV, hd), -1e4, jnp.float32)
    kn = _rand(ks[1], (B, KV, 1, hd))
    vn = _rand(ks[2], (B, KV, 1, hd))
    tables = jnp.zeros((B, NBT), jnp.int32)
    idx = jnp.zeros((B,), jnp.int32)
    out = paged_raw(q, kp, vp, kn, vn, tables, idx, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out),
                               np.broadcast_to(np.asarray(vn), out.shape),
                               atol=1e-6)


# ------------------------------------------- dead blocks are never read

def _poisoned_problem(seed, BS, NBT, lengths, int8):
    """Raw-kernel operands whose every whole block past a row's live
    length, and the junk block 0, is NaN (int8: NaN scales), plus the
    clean pool the oracle sees.  Live blocks are distinct physical ids;
    a row's dead entries are the junk block or NaN blocks of their own."""
    B, KV, G, hd = len(lengths), 2, 3, 32
    NP = B * NBT + 1
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, KV, G, hd), jnp.float32)
    kn = jnp.asarray(rng.randn(B, KV, 1, hd), jnp.float32)
    vn = jnp.asarray(rng.randn(B, KV, 1, hd), jnp.float32)
    tables = 1 + rng.permutation(B * NBT).reshape(B, NBT)
    tables[-1, -(-lengths[-1] // BS):] = 0     # last row: dead -> junk
    dead = np.zeros(NP, bool)
    dead[0] = True
    for b, n in enumerate(lengths):
        dead[tables[b, -(-n // BS):]] = True
    poison = jnp.asarray(dead)[:, None, None, None]
    if int8:
        pools = [quantize_int8(jnp.asarray(rng.randn(NP, BS, KV, hd),
                                           jnp.float32), axis=-1)
                 for _ in range(2)]
        clean = (pools[0][0], pools[0][1], pools[1][0], pools[1][1])
        bad = (clean[0], jnp.where(poison, jnp.nan, clean[1]),
               clean[2], jnp.where(poison, jnp.nan, clean[3]))
    else:
        clean = tuple(jnp.asarray(rng.randn(NP, BS, KV, hd), jnp.bfloat16)
                      for _ in range(2))
        bad = tuple(jnp.where(poison, jnp.nan, c) for c in clean)
    return (q, bad, clean, kn, vn, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("BS,NBT,lengths", [
    (8, 4, (8, 16, 24)),            # lengths exactly on a block boundary
    (8, 4, (9, 17, 25)),            # one position past a boundary
    (64, 6, (321, 130, 200)),       # NBT 6 over groups of 4 blocks
    (32, 11, (352, 259, 0)),        # every block live beside length 0
], ids=["boundary", "one_past", "ragged_groups", "full_and_empty"])
def test_dead_blocks_are_never_read(BS, NBT, lengths, int8):
    """NaN in every dead block must not reach the output: a kernel that
    read one would leak it through ``0 * NaN`` in ``p @ v``."""
    from repro.kernels.gqa_decode import group_blocks, paged_gqa_decode_int8
    if BS == 64:                    # the last group is a partial one
        assert NBT % group_blocks(BS, NBT)
    q, bad, clean, kn, vn, tables, idx = _poisoned_problem(
        BS * NBT, BS, NBT, list(lengths), int8)
    if int8:
        got = paged_gqa_decode_int8(q, bad[0], bad[1], bad[2], bad[3], kn,
                                    vn, tables, idx, interpret=True)
        want = ref.paged_decode_attention_int8_ref(q, *clean, kn, vn,
                                                   tables, idx)
    else:
        got = paged_raw(q, *bad, kn, vn, tables, idx, interpret=True)
        want = ref.paged_decode_attention_ref(
            q, *(c.astype(jnp.float32) for c in clean), kn, vn, tables, idx)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_dead_table_entries_are_never_dereferenced(int8):
    """Every table entry at or past ``cdiv(length, BS)`` points outside
    the pool.  TPU interpret mode raises on an out-of-bounds DMA, so the
    kernel passes only if it copies no dead block at all — the NaN test
    above shows none reaches the output, this one that none is read."""
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels.gqa_decode import paged_gqa_decode_int8
    BS, NBT, lengths = 8, 6, [9, 48, 0]
    q, _, clean, kn, vn, tables, idx = _poisoned_problem(
        3, BS, NBT, lengths, int8)
    NP = clean[0].shape[0]
    dead = np.arange(NBT)[None, :] >= -(-np.asarray(lengths)[:, None] // BS)
    off_pool = jnp.where(jnp.asarray(dead), NP + 1000, tables)
    try:
        if int8:
            got = paged_gqa_decode_int8(q, *clean, kn, vn, off_pool, idx,
                                        interpret=pltpu.InterpretParams())
            want = ref.paged_decode_attention_int8_ref(q, *clean, kn, vn,
                                                       tables, idx)
        else:
            got = paged_raw(q, *clean, kn, vn, off_pool, idx,
                            interpret=pltpu.InterpretParams())
            want = ref.paged_decode_attention_ref(
                q, *(c.astype(jnp.float32) for c in clean), kn, vn, tables,
                idx)
    finally:
        pltpu.reset_tpu_interpret_mode_state()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ----------------------------------------------------- property sweep

@pytest.mark.slow
def test_paged_kernel_property_sweep():
    """hypothesis-optional randomized shape/length sweep (slow tier)."""
    hypothesis = pytest.importorskip(
        "hypothesis", reason="property tests need the hypothesis test dep")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.integers(1, 4), st.integers(1, 4), st.sampled_from([16, 32]),
           st.sampled_from([4, 8]), st.integers(1, 4), st.integers(0, 1000),
           st.randoms(use_true_random=False))
    def run(B, KV, hd, BS, NBT, seed, rnd):
        group = rnd.randint(1, 3)
        Hp = KV * group + rnd.randint(0, 1)     # sometimes non-uniform
        kv_idx = None if Hp == KV else np.minimum(
            np.arange(Hp) // group, KV - 1)
        NP = B * NBT + 1
        lengths = [rnd.randint(0, NBT * BS - 1) for _ in range(B)]
        q = _rand(jax.random.PRNGKey(seed), (B, 1, Hp, hd))
        kp, vp, tables, kn, vn, idx = _pool_problem(
            seed, B, KV, hd, NP, BS, NBT, lengths)
        got = ops.paged_gqa_decode(
            q, kp, vp, kn, vn, tables, idx,
            kv_index=None if kv_idx is None
            else tuple(int(i) for i in kv_idx))
        want = _dense_equivalent(q, kp, vp, tables, idx, kn, vn, kv_idx)
        np.testing.assert_allclose(np.asarray(got), want,
                                   atol=3e-5, rtol=3e-5)

    run()
