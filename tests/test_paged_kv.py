"""Paged KV cache: paged-vs-dense token equivalence, block-pool
exhaustion backpressure, free-list reuse under churn, preempt/resume,
fragmentation accounting, and stop-token capacity release."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, reduced
from repro.core.binary import shape_key
from repro.core.function import FunctionRegistry
from repro.core.runtime import XarTrekRuntime
from repro.serve import (BlockPool, ContinuousBatchingEngine,
                         GenerationRequest, PagedSlotManager, ServeEngine,
                         SlotManager)

def _serve(engine, reqs=()):
    """v2 run() flattened to the old {req_id: token-array} shape."""
    return {rid: out.tokens for rid, out in engine.run(reqs).items()}



@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(reduced(ARCHS["smollm-135m"]),
                               dtype="float32")


@pytest.fixture(scope="module")
def sync_engine(cfg):
    return ServeEngine(cfg, seed=0)


def _prompts(cfg, B, S, seed=0):
    key = jax.random.PRNGKey(seed)
    return jax.random.randint(key, (B, S), 0, cfg.vocab_size, jnp.int32)


# ------------------------------------------------------------- equivalence

def test_paged_tokens_match_dense_and_sync(cfg, sync_engine):
    """Byte-identical greedy tokens across all three engines when the
    paged attention span (table_width * block_size) equals max_seq."""
    prompts = _prompts(cfg, B=4, S=12)
    want = sync_engine.generate(prompts, max_new_tokens=6).tokens
    dense = ContinuousBatchingEngine(cfg, max_slots=4, max_seq=64,
                                     params=sync_engine.params)
    paged = ContinuousBatchingEngine(cfg, max_slots=4, max_seq=64,
                                     params=sync_engine.params,
                                     paged=True, block_size=16)
    got_dense = dense.generate(np.asarray(prompts), max_new_tokens=6)
    got_paged = paged.generate(np.asarray(prompts), max_new_tokens=6)
    np.testing.assert_array_equal(want, got_dense)
    np.testing.assert_array_equal(want, got_paged)


def test_paged_mixed_lengths_match_dense(cfg, sync_engine):
    """Ragged arrivals (mixed prompt/gen lengths) through paged and dense
    engines produce the same per-request tokens."""
    rng2 = np.random.RandomState(7)
    reqs_a = [GenerationRequest(rng2.randint(0, cfg.vocab_size,
                                   size=int(rng2.randint(3, 20))),
                      max_new_tokens=int(rng2.randint(1, 8)),
                      arrival_s=0.004 * i) for i in range(6)]
    rng2 = np.random.RandomState(7)
    reqs_b = [GenerationRequest(rng2.randint(0, cfg.vocab_size,
                                   size=int(rng2.randint(3, 20))),
                      max_new_tokens=int(rng2.randint(1, 8)),
                      arrival_s=0.004 * i) for i in range(6)]
    dense = ContinuousBatchingEngine(cfg, max_slots=3, max_seq=64,
                                     params=sync_engine.params)
    paged = ContinuousBatchingEngine(cfg, max_slots=3, max_seq=64,
                                     params=sync_engine.params,
                                     paged=True, block_size=16)
    out_a = _serve(dense, reqs_a)
    out_b = _serve(paged, reqs_b)
    for ra, rb in zip(reqs_a, reqs_b):
        np.testing.assert_array_equal(out_a[ra.req_id], out_b[rb.req_id])


# ----------------------------------------------------- pool + backpressure

def test_block_pool_alloc_free_exhaustion():
    pool = BlockPool(num_blocks=3, block_size=8)
    a = pool.alloc(2)
    assert 0 not in a                     # junk block never handed out
    assert pool.free_blocks() == 1
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(2)
    pool.free(a)
    assert pool.free_blocks() == 3
    assert pool.stats == {"allocated": 2, "freed": 2, "peak_in_use": 2,
                          "cache_hits": 0, "evicted": 0}


def test_block_exhaustion_backpressure_gates_admission(cfg, sync_engine):
    """Pool smaller than max_slots' worst case: admission waits on blocks
    (not on rows) and nothing is preempted when the watermark holds."""
    rng = np.random.RandomState(11)
    # each request: 2 prompt blocks + 1 growth block = 3 of the 6-block
    # pool; admission watermark lets exactly two run concurrently
    reqs = [GenerationRequest(rng.randint(0, cfg.vocab_size, size=16),
                    max_new_tokens=8) for _ in range(4)]
    eng = ContinuousBatchingEngine(cfg, max_slots=4, max_seq=32,
                                   params=sync_engine.params,
                                   paged=True, block_size=8, num_blocks=6)
    out = _serve(eng, reqs)
    assert sorted(out) == sorted(r.req_id for r in reqs)
    st = eng.slots.stats
    assert st["admitted"] == 4 and st["released"] == 4
    assert st["peak_active"] == 2          # blocks, not rows, were binding
    assert st["preempted"] == 0
    assert eng.slots.pool.blocks_in_use() == 0


def test_overlong_paged_request_rejected_at_submission(cfg, sync_engine):
    eng = ContinuousBatchingEngine(cfg, max_slots=2, max_seq=32,
                                   params=sync_engine.params,
                                   paged=True, block_size=8, num_blocks=3)
    # 3-block pool: a request needing 4 blocks can never run
    with pytest.raises(ValueError, match="blocks"):
        eng.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=16)
    # engine stays usable
    out = eng.generate(np.arange(1, 9, dtype=np.int32)[None, :],
                       max_new_tokens=2)
    assert out.shape == (1, 2)


def test_block_freelist_reuse_under_churn(cfg, sync_engine):
    """Sequential waves through a small pool recycle the same physical
    blocks; the pool drains back to empty."""
    rng = np.random.RandomState(13)
    reqs = [GenerationRequest(rng.randint(0, cfg.vocab_size, size=8),
                    max_new_tokens=4) for _ in range(6)]
    eng = ContinuousBatchingEngine(cfg, max_slots=2, max_seq=32,
                                   params=sync_engine.params,
                                   paged=True, block_size=8, num_blocks=4)
    out = _serve(eng, reqs)
    assert len(out) == 6
    pst = eng.slots.pool.stats
    assert pst["allocated"] == pst["freed"]
    assert pst["allocated"] > eng.slots.pool.num_blocks   # ids were reused
    assert pst["peak_in_use"] <= eng.slots.pool.num_blocks
    assert eng.slots.pool.blocks_in_use() == 0


def test_preemption_resumes_byte_identical(cfg, sync_engine):
    """A pool too small for two long generations forces a preempt +
    resume-by-recompute; greedy tokens still match the dense engine."""
    rng = np.random.RandomState(3)
    p1 = rng.randint(0, cfg.vocab_size, size=4)
    p2 = rng.randint(0, cfg.vocab_size, size=4)
    dense = ContinuousBatchingEngine(cfg, max_slots=2, max_seq=24,
                                     params=sync_engine.params)
    da, db = GenerationRequest(p1, 12), GenerationRequest(p2, 12)
    want = _serve(dense, [da, db])
    small = ContinuousBatchingEngine(cfg, max_slots=2, max_seq=24,
                                     params=sync_engine.params,
                                     paged=True, block_size=4, num_blocks=6)
    ra, rb = GenerationRequest(p1, 12), GenerationRequest(p2, 12)
    got = _serve(small, [ra, rb])
    assert small.slots.stats["preempted"] >= 1
    np.testing.assert_array_equal(want[da.req_id], got[ra.req_id])
    np.testing.assert_array_equal(want[db.req_id], got[rb.req_id])
    assert small.slots.pool.blocks_in_use() == 0


# ------------------------------------------------------ capacity headline

def test_paged_admits_more_concurrent_at_equal_memory(cfg, sync_engine):
    """Same KV budget (144 positions), short requests: dense caps at 3
    rows, the paged pool runs 6 concurrently."""
    rng = np.random.RandomState(5)
    dense = ContinuousBatchingEngine(cfg, max_slots=3, max_seq=48,
                                     params=sync_engine.params)
    paged = ContinuousBatchingEngine(cfg, max_slots=6, max_seq=48,
                                     params=sync_engine.params,
                                     paged=True, block_size=16,
                                     num_blocks=9)   # 9*16 = 144 = 3*48
    _serve(dense, [GenerationRequest(rng.randint(0, cfg.vocab_size, size=4),
                         max_new_tokens=4) for _ in range(6)])
    _serve(paged, [GenerationRequest(rng.randint(0, cfg.vocab_size, size=4),
                         max_new_tokens=4) for _ in range(6)])
    assert dense.slots.stats["peak_active"] == 3
    assert paged.slots.stats["peak_active"] == 6
    assert paged.slots.stats["preempted"] == 0


# -------------------------------------------------- fragmentation stats

def test_fragmentation_accounting_dense_vs_paged():
    req = GenerationRequest(np.arange(1, 5, dtype=np.int32), max_new_tokens=4)
    dense = SlotManager(max_slots=2, max_seq=64)
    dense.admit(dataclasses.replace(req), first_token=7)
    dst = dense.stats
    assert dst["reserved_positions"] == 64      # whole row held
    assert dst["used_positions"] == 4
    assert dst["frag_positions"] == 60

    paged = PagedSlotManager(max_slots=2, block_size=8, num_blocks=16,
                             max_seq=64)
    blocks = paged.pool.alloc(paged.blocks_for(4))
    paged.admit(dataclasses.replace(req), first_token=7, blocks=blocks)
    pst = paged.stats
    assert pst["reserved_positions"] == 8       # one block held
    assert pst["used_positions"] == 4
    assert pst["frag_positions"] == 4           # < block_size, bounded


def test_paged_manager_without_max_seq_is_pool_bound():
    m = PagedSlotManager(max_slots=2, block_size=8, num_blocks=4,
                         max_seq=None)
    assert m.table_width == 4
    m.validate(GenerationRequest(np.arange(1, 9, dtype=np.int32),
                       max_new_tokens=24))      # 32 positions = whole pool
    with pytest.raises(ValueError, match="blocks"):
        m.validate(GenerationRequest(np.arange(1, 9, dtype=np.int32),
                           max_new_tokens=25))


# -------------------------------------------------------- stop tokens

def test_stop_token_ends_generation_early(cfg, sync_engine):
    prompt = np.arange(1, 6, dtype=np.int32)
    base = ContinuousBatchingEngine(cfg, max_slots=1, max_seq=32,
                                    params=sync_engine.params)
    full = list(_serve(base, [GenerationRequest(prompt, 6)]).values())[0].tolist()
    stop = full[1]
    expect_len = full.index(stop) + 1
    eng = ContinuousBatchingEngine(cfg, max_slots=1, max_seq=32,
                                   params=sync_engine.params,
                                   paged=True, block_size=8)
    out = list(_serve(eng, [GenerationRequest(prompt, 6,
                                  stop_tokens=(stop,))]).values())[0]
    assert out.tolist() == full[:expect_len]    # stop token included
    assert len(out) < len(full)


def test_early_stop_releases_capacity_to_queued_arrivals(cfg, sync_engine):
    """With one slot, A stopping early hands the slot (and its blocks) to
    queued B sooner: fewer total decode steps than the no-stop run."""
    pa = np.arange(1, 6, dtype=np.int32)
    pb = np.arange(2, 7, dtype=np.int32)
    ref = ContinuousBatchingEngine(cfg, max_slots=1, max_seq=32,
                                   params=sync_engine.params,
                                   paged=True, block_size=8)
    out_ref = _serve(ref, [GenerationRequest(pa, 6), GenerationRequest(pb, 6)])
    a_toks = [v for k, v in sorted(out_ref.items())][0].tolist()
    stop = a_toks[1]
    eng = ContinuousBatchingEngine(cfg, max_slots=1, max_seq=32,
                                   params=sync_engine.params,
                                   paged=True, block_size=8)
    ra = GenerationRequest(pa, 6, stop_tokens=(stop,))
    rb = GenerationRequest(pb, 6)
    out = _serve(eng, [ra, rb])
    assert len(out) == 2
    assert len(out[ra.req_id]) < 6
    np.testing.assert_array_equal(out[rb.req_id],
                                  out_ref[sorted(out_ref)[1]])
    assert eng.stats["decode_steps"] < ref.stats["decode_steps"]
    assert eng.slots.pool.blocks_in_use() == 0
    st = eng.slots.stats
    assert st["admitted"] == 2 and st["released"] == 2


# -------------------------------------------------------- decode span

def test_decode_span_counts_live_blocks(cfg, sync_engine, tmp_path):
    """``engine.decode`` carries ``blocks``: the pool blocks its rows'
    positions fill, ``sum(cdiv(pos, block_size))`` — the blocks the
    paged decode kernel walks, against ``rows * table width`` reserved."""
    from jax.profiler import ProfileData
    eng = ContinuousBatchingEngine(cfg, max_slots=3, max_seq=64,
                                   params=sync_engine.params, paged=True,
                                   block_size=16)
    want, ensure = [], eng._ensure_decode_blocks

    def noting():
        ensure()
        want.append(sum(-(-s.pos // 16)
                        for s in eng.slots.active_slots()))

    eng._ensure_decode_blocks = noting
    rng = np.random.RandomState(3)
    reqs = [GenerationRequest(rng.randint(0, cfg.vocab_size, size=n),
                              max_new_tokens=k)
            for n, k in ((5, 20), (16, 6), (30, 12))]
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    got = [int(dict(e.stats)["blocks"])
           for p in ProfileData.from_file(str(path)).planes
           for line in p.lines for e in line.events
           if e.name == "engine.decode"]
    assert got == want and max(want) > len(reqs)


# ------------------------------------------------------- runtime/compile

def test_paged_decode_static_signature_no_bucket_misses(cfg, sync_engine):
    """Steady-state paged decode (tokens + index + block table) is one
    static shape: the prepare()-time compile serves every step, so
    Algorithm 1 timing never sees a decode compile."""
    rt = XarTrekRuntime(registry=FunctionRegistry(),
                        min_reconfig_seconds=0.0)
    eng = ContinuousBatchingEngine(cfg, max_slots=2, max_seq=32,
                                   params=sync_engine.params, runtime=rt,
                                   fn_prefix="pgd", paged=True, block_size=8)
    rng = np.random.RandomState(17)
    reqs = [GenerationRequest(rng.randint(0, cfg.vocab_size, size=6),
                    max_new_tokens=3) for _ in range(4)]
    out = _serve(eng, reqs)
    assert len(out) == 4
    decode_calls = [r for r in rt.call_log if r["fn"] == "pgd_decode"]
    assert decode_calls
    assert rt.binaries["pgd_decode"].shape_stats["misses"] == 0


def test_shape_key_handles_scalar_leaves():
    a = shape_key((jnp.zeros((2, 3)), {"n": 3}))
    b = shape_key((jnp.zeros((2, 3)), {"n": 4}))
    c = shape_key((jnp.zeros((2, 3)), {"n": 3}))
    assert a != b and a == c
    assert len({a, b, c}) == 2             # hashable, usable as dict keys
