"""Chip-compiler rehearsals: the serve path's Pallas kernels, lowered
natively for a DESCRIBED TPU v5e at smollm-135m's published widths.

Nothing runs: each test compiles for a chip that is not attached, so it
catches what interpret mode cannot — block shapes the TPU lowering
refuses, VMEM overuse, a program that does not fit the device.  Shapes:
8 decode slots, 9 query / 3 kv heads, head_dim 64 with the pool
lane-padded to 128, 32-position blocks, 2048-token context
(``configs/smollm_135m.py``); the paged decode also at yi-6b's widths
over the benchmark's 512- and 4096-position tables.

The topology is described inside a module fixture (the TPU library
loads there, in the worker that runs this file, never at import time),
and the persistent compilation cache is off around these compiles: an
entry written for a described chip cannot be read back without one.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.kernels import ops

CFG = ARCHS["smollm-135m"]
B, BS, MAX_SEQ = 8, 32, 2048
HP, KV, HD = CFG.num_heads, CFG.num_kv_heads, CFG.resolved_head_dim
HDP = 128                                   # lane-aligned pool width
NBT = MAX_SEQ // BS
NP = B * NBT + 1                            # usable blocks + junk block 0
KV_INDEX = tuple(h * KV // HP for h in range(HP))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    """Lower + compile natively; returns the optimized HLO text."""
    return jax.jit(fn).lower(*specs).compile().as_text()


def _kernel_names(hlo: str) -> set:
    """Names of the Pallas kernels in compiled HLO: each
    ``tpu_custom_call`` instruction's name without its instance number,
    which is the op name a device trace shows for it."""
    return {re.sub(r"(\.\d+)+$", "", line.split(" = ", 1)[0].strip()
                   .removeprefix("ROOT ").lstrip("%"))
            for line in hlo.splitlines()
            if "custom_call_target=\"tpu_custom_call\"" in line}


def _pool(sharding, int8: bool):
    """(k, v) page specs plus int8 scale specs (None for bf16)."""
    dt = jnp.int8 if int8 else jnp.bfloat16
    pages = _spec(sharding, (NP, BS, KV, HDP), dt)
    scales = _spec(sharding, (NP, BS, KV, 1), jnp.float32) if int8 else None
    return pages, scales


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_paged_decode_compiles(one_chip, int8):
    S = lambda shape, dt=jnp.bfloat16: _spec(one_chip, shape, dt)
    pages, scales = _pool(one_chip, int8)
    q, tok = S((B, 1, HP, HD)), S((B, 1, KV, HD))
    tables, idx = S((B, NBT), jnp.int32), S((B,), jnp.int32)
    if int8:
        fn = lambda q, k, ks, v, vs, kn, vn, t, i: ops.paged_gqa_decode_int8(
            q, k, ks, v, vs, kn, vn, t, i, kv_index=KV_INDEX,
            interpret=False)
        hlo = _compile(fn, q, pages, scales, pages, scales, tok, tok,
                       tables, idx)
    else:
        fn = lambda q, k, v, kn, vn, t, i: ops.paged_gqa_decode(
            q, k, v, kn, vn, t, i, kv_index=KV_INDEX, interpret=False)
        hlo = _compile(fn, q, pages, pages, tok, tok, tables, idx)
    assert "tpu_custom_call" in hlo
    assert _kernel_names(hlo) == {
        "paged_gqa_decode_int8" if int8 else "paged_gqa_decode"}


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("nbt", [16, 128], ids=["pool512", "pool4096"])
def test_paged_decode_compiles_at_yi6b_widths(one_chip, nbt, int8):
    """The paged decode at yi-6b's widths (32 slots, 32/4 heads,
    head_dim 128, 32-position blocks) over a 512- and a 4096-position
    table: the one-grid-step-per-row kernel's group buffers fit VMEM."""
    yi = ARCHS["yi-6b"]
    hp, kv, hd, b = yi.num_heads, yi.num_kv_heads, yi.resolved_head_dim, 32
    S = lambda shape, dt=jnp.bfloat16: _spec(one_chip, shape, dt)
    pages = S((b * nbt + 1, BS, kv, hd), jnp.int8 if int8 else jnp.bfloat16)
    scales = S((b * nbt + 1, BS, kv, 1), jnp.float32)
    q, tok = S((b, 1, hp, hd)), S((b, 1, kv, hd))
    tables, idx = S((b, nbt), jnp.int32), S((b,), jnp.int32)
    kv_index = tuple(h * kv // hp for h in range(hp))
    if int8:
        fn = lambda q, k, ks, v, vs, kn, vn, t, i: ops.paged_gqa_decode_int8(
            q, k, ks, v, vs, kn, vn, t, i, kv_index=kv_index,
            interpret=False)
        hlo = _compile(fn, q, pages, scales, pages, scales, tok, tok,
                       tables, idx)
    else:
        fn = lambda q, k, v, kn, vn, t, i: ops.paged_gqa_decode(
            q, k, v, kn, vn, t, i, kv_index=kv_index, interpret=False)
        hlo = _compile(fn, q, pages, pages, tok, tok, tables, idx)
    assert _kernel_names(hlo) == {
        "paged_gqa_decode_int8" if int8 else "paged_gqa_decode"}


@pytest.mark.parametrize("op,width", [
    ("prefill", 256), ("prefill_int8", 256),
    ("verify", 5), ("verify_int8", 5)])
def test_paged_prefill_at_offset_compiles(one_chip, op, width):
    """Chunk prefill at offset (a 256-token chunk budget) and the verify
    re-export (k=4 drafts + the committed last token)."""
    S = lambda shape, dt=jnp.bfloat16: _spec(one_chip, shape, dt)
    int8 = op.endswith("int8")
    pages, scales = _pool(one_chip, int8)
    q, chunk = S((B, width, HP, HD)), S((B, width, KV, HD))
    tables = S((B, NBT), jnp.int32)
    off = ln = S((B,), jnp.int32)
    kernel = getattr(ops, f"paged_gqa_{op}")
    if int8:
        fn = lambda q, k, ks, v, vs, kn, vn, t, o, n: kernel(
            q, k, ks, v, vs, kn, vn, t, o, n, kv_index=KV_INDEX,
            interpret=False)
        hlo = _compile(fn, q, pages, scales, pages, scales, chunk, chunk,
                       tables, off, ln)
    else:
        fn = lambda q, k, v, kn, vn, t, o, n: kernel(
            q, k, v, kn, vn, t, o, n, kv_index=KV_INDEX, interpret=False)
        hlo = _compile(fn, q, pages, pages, chunk, chunk, tables, off, ln)
    assert "tpu_custom_call" in hlo
    # verify re-exports the prefill-at-offset kernel
    assert _kernel_names(hlo) == {
        "paged_gqa_prefill_int8" if int8 else "paged_gqa_prefill"}


@pytest.mark.parametrize("kernel", [
    "paged_gqa_decode", "paged_gqa_decode_int8", "paged_gqa_prefill",
    "paged_gqa_prefill_int8"])
def test_paged_kernels_carry_their_names(one_chip, kernel):
    """Each paged kernel names itself (``pallas_call(name=...)``), called
    bare, outside the named jit of its ``ops`` wrapper: the op name a
    device trace shows does not hang on the wrapper's name."""
    from repro.kernels import gqa_decode, gqa_prefill
    S = lambda shape, dt=jnp.bfloat16: _spec(one_chip, shape, dt)
    G, W = HP // KV, 256
    int8 = kernel.endswith("int8")
    pages, scales = _pool(one_chip, int8)
    pool = (pages, scales, pages, scales) if int8 else (pages, pages)
    tables, idx = S((B, NBT), jnp.int32), S((B,), jnp.int32)
    if "decode" in kernel:
        fn = getattr(gqa_decode, kernel)
        tok = S((B, KV, 1, HDP))
        args = (S((B, KV, G, HDP)), *pool, tok, tok, tables, idx)
        kw = {}
    else:
        fn = getattr(gqa_prefill, kernel)
        chunk = S((B, W, KV, HDP))
        args = (S((B, KV, W * G, HDP)), *pool, chunk, chunk, tables, idx,
                idx)
        kw = {"group": G}
    hlo = _compile(lambda *a: fn(*a, interpret=False, **kw), *args)
    assert _kernel_names(hlo) == {kernel}


def test_dense_decode_compiles(one_chip):
    S = lambda shape, dt=jnp.bfloat16: _spec(one_chip, shape, dt)
    fn = lambda q, k, v, i: ops.gqa_decode(q, k, v, i, kv_index=KV_INDEX,
                                           interpret=False)
    hlo = _compile(fn, S((B, 1, HP, HD)), S((B, MAX_SEQ, KV, HD)),
                   S((B, MAX_SEQ, KV, HD)), S((B,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_ragged_draft_decode_compiles(one_chip):
    """The speculative draft chain's decode: a dense f32 draft row of
    max_seq + 4 positions rounded up to whole 128-position blocks."""
    from repro.serve.engine import DRAFT_BLOCK
    width = -(-(MAX_SEQ + 4) // DRAFT_BLOCK) * DRAFT_BLOCK
    S = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)
    fn = lambda q, k, v, i, kn, vn: ops.gqa_decode_ragged(
        q, k, v, i, kn, vn, kv_index=KV_INDEX, interpret=False)
    hlo = _compile(fn, S((B, 1, HP, HD)), S((B, width, KV, HD)),
                   S((B, width, KV, HD)), S((B,), jnp.int32),
                   S((B, 1, KV, HD)), S((B, 1, KV, HD)))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("seq", [128, 512])
def test_flash_prefill_compiles(one_chip, seq):
    S = lambda shape: _spec(one_chip, shape, jnp.bfloat16)
    fn = lambda q, k, v: ops.flash_attention(q, k, v, kv_index=KV_INDEX,
                                             interpret=False)
    hlo = _compile(fn, S((1, seq, HP, HD)), S((1, seq, KV, HD)),
                   S((1, seq, KV, HD)))
    assert "tpu_custom_call" in hlo


def test_full_width_paged_decode_step_compiles(one_chip, monkeypatch):
    """One whole ACCEL decode step of the paged engine: 30 layers over a
    lane-aligned bf16 pool of 8 x 2048 tokens, with in-graph sampling —
    the program the chip runs every decode step, and its device memory
    (params + pool + temporaries) must fit one v5e's 16 GB."""
    from repro.models.model import build_model
    from repro.models.sampling import sampling_leaves
    from repro.serve.api import SamplingParams
    monkeypatch.setenv(ops.INTERPRET_ENV, "0")     # lower Pallas natively
    model = build_model(CFG, None)
    S = lambda a: _spec(one_chip, a.shape, a.dtype)
    params = jax.tree.map(S, jax.eval_shape(model.init,
                                            jax.random.PRNGKey(0)))
    cache = jax.tree.map(S, jax.eval_shape(
        lambda: model.init_paged_cache(NP, BS, lane_align=True)))
    batch = {"tokens": _spec(one_chip, (B, 1), jnp.int32),
             "index": _spec(one_chip, (B,), jnp.int32),
             "block_table": _spec(one_chip, (B, NBT), jnp.int32),
             **{k: S(v) for k, v in sampling_leaves(SamplingParams(),
                                                    B).items()}}
    step = jax.jit(lambda p, c, b: model.decode_sampled(p, c, b,
                                                        backend="pallas"),
                   donate_argnums=(1,))
    compiled = step.lower(params, cache, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().peak_memory_in_bytes < 16e9
