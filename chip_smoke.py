#!/usr/bin/env python3
"""Chip smoke test: serve smollm-135m at its published widths on a TPU.

The served path is ``ContinuousBatchingEngine(paged=True,
prefix_cache=True, prefill_tokens_per_step=256)`` under an
``XarTrekRuntime``, with random bf16 weights from a seed.  Every step
function has a HOST build (XLA) and an ACCEL build (Pallas kernels,
compiled natively for the chip).

One chip (``python chip_smoke.py``), all in this one process:

1. the same 8 requests (prompts of 128-1024 tokens, two sharing a
   512-token prefix, 64 new tokens each, greedy) served three times:
   pinned ``PinHost``, pinned ``PinAccel``, and under a scripted
   HOST -> ACCEL -> HOST decode flip mid-stream;
2. a short ``PinAccel`` pass with speculative decoding (draft + verify);
3. a short ``PinAccel`` pass over an int8 KV pool.

It fails (non-zero exit, no result line) when no TPU is present, when
Pallas would run in interpret mode, when any phase raises, when an
ACCEL build it pinned served no call, or when a request's first token
differs between HOST and ACCEL without being a near-tie (see
``NEAR_TIE_NATS``) under a float32 reference forward.

Four chips (``python chip_smoke.py --chips 4``): a threaded
``ClusterFrontEnd`` with four replicas, each with its params, KV pool
and compiled builds on its own chip, every replica serving the same
requests, against one engine serving them alone; each replica's tokens
must equal that run's.  No other phase runs.

The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``; everything before it is the report.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MODEL = "smollm-135m"
MAX_SLOTS = 8
MAX_SEQ = 2048             # the model's context length
BLOCK = 32
CHUNK = 256                # prefill_tokens_per_step
MIN_BUCKET = 128           # every prefill chunk pads to 128 or 256 tokens
NEW_TOKENS = 64
# wave 1: seven prompts (the 1024-token one is chunked 4 x 256); wave 2:
# one prompt whose first 512 tokens repeat wave 1's first prompt, so it
# is admitted against 16 cached blocks (the prefix-cache hit path)
WAVE1_LENS = (1024, 640, 384, 128, 200, 900, 512)
SHARED, WAVE2_LEN = 512, 700
SPEC_LENS = (40, 64, 96)           # spec pass: draft rows stay one bucket
INT8_LENS = (384, 100)             # int8 pass: one chunked, one single
SEED = 0
# A first token may differ between HOST and ACCEL only when the two
# candidates are a near-tie: their logprobs under a float32 reference
# forward lie within this many nats.  Reason: the builds emit bf16
# logits, and bf16 spaces values of magnitude 4-8 (where this model's
# top logits sit) 2^-5 ~ 0.031 apart, so distinct candidates can round
# to one value; on top of that each build rounds every activation of 30
# layers in its own order, which moved the top-100 logprobs of a
# 30-layer bf16 forward up to ~0.06 nats from the float32 reference
# (XLA CPU, this model's widths).  0.1 nats is three bf16 steps; a
# broken kernel moves logits by whole nats.
NEAR_TIE_NATS = 0.1
FLIP_AT = (8, 24)          # decode decisions (8, 24] on ACCEL, HOST around


def log(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass(frozen=True)
class Setup:
    """Sizes of one smoke run (the chip run uses the module constants;
    a CPU rehearsal passes a reduced model and shorter prompts)."""

    cfg: object
    max_seq: int = MAX_SEQ
    new_tokens: int = NEW_TOKENS
    wave1: tuple = WAVE1_LENS
    shared: int = SHARED
    wave2: int = WAVE2_LEN
    spec_lens: tuple = SPEC_LENS
    int8_lens: tuple = INT8_LENS
    min_bucket: int = MIN_BUCKET
    chunk: int = CHUNK
    flip_at: tuple = FLIP_AT


class FlipSchedule:
    """Scripted policy: decode decisions ``(at[0], at[1]]`` on ACCEL,
    HOST before and after; every other step on HOST."""

    name = "flip_schedule"

    def __init__(self, at):
        self.at = at
        self.decodes = 0

    def decide(self, signals, row, residency):
        from repro.core.policy import Decision
        from repro.core.targets import TargetKind
        if not row.app.endswith("_decode"):
            return Decision(TargetKind.HOST)
        self.decodes += 1
        if self.at[0] < self.decodes <= self.at[1]:
            return Decision(TargetKind.ACCEL)
        return Decision(TargetKind.HOST)


def prompts(setup: Setup, lens, seed: int) -> list[np.ndarray]:
    rng = np.random.RandomState(seed)
    return [rng.randint(0, setup.cfg.vocab_size, size=n).astype(np.int32)
            for n in lens]


def main_waves(setup: Setup) -> tuple[list, list]:
    w1 = prompts(setup, setup.wave1, SEED)
    tail = prompts(setup, (setup.wave2 - setup.shared,), SEED + 1)[0]
    return w1, [np.concatenate([w1[0][:setup.shared], tail])]


def make_engine(setup: Setup, params, policy, *, cfg=None, **kw):
    from repro.core.function import FunctionRegistry
    from repro.core.runtime import XarTrekRuntime
    from repro.serve import ContinuousBatchingEngine
    rt = XarTrekRuntime(registry=FunctionRegistry())
    eng = ContinuousBatchingEngine(
        cfg or setup.cfg, max_slots=MAX_SLOTS, max_seq=setup.max_seq,
        params=params, runtime=rt, policy=policy, paged=True,
        block_size=BLOCK, prefix_cache=True,
        prefill_tokens_per_step=setup.chunk, min_bucket=setup.min_bucket,
        **kw)
    return eng, rt


def serve(engine, waves, new_tokens: int) -> list:
    """Serve each wave to completion in order; outputs in prompt order."""
    from repro.serve import GenerationRequest, SamplingParams
    outs = []
    for wave in waves:
        reqs = [GenerationRequest(p, max_new_tokens=new_tokens,
                                  sampling=SamplingParams(logprobs=True))
                for p in wave]
        res = engine.run(reqs)
        outs += [res[r.req_id] for r in reqs]
    return outs


def calls(rt) -> dict:
    """{function suffix: {target: calls}} from the runtime summary."""
    per = rt.summary()["per_function"]
    return {name.split("_", 1)[1]: dict(v["calls"])
            for name, v in per.items()}


def report_runtime(tag: str, rt) -> None:
    per = rt.summary()["per_function"]
    for name, v in sorted(per.items()):
        comp = {t: f"{c['compiles']}x {c['compile_seconds']:.2f}s"
                for t, c in v["compiles"].items()}
        log(f"[{tag}] {name}: calls={v['calls']} "
            f"migrations={v['migrations']} compiles={comp}")


def require_accel_calls(tag: str, rt, names) -> None:
    got = calls(rt)
    for n in names:
        if got.get(n, {}).get("accel", 0) < 1:
            raise RuntimeError(f"[{tag}] ACCEL build of {n!r} served no "
                               f"call: {got}")


def check_tokens(tag: str, outs, vocab: int) -> None:
    for o in outs:
        toks = np.asarray(o.tokens)
        if toks.size == 0 or toks.min() < 0 or toks.max() >= vocab:
            raise RuntimeError(f"[{tag}] bad tokens {toks}")
        if not np.isfinite(o.logprobs).all():
            raise RuntimeError(f"[{tag}] non-finite logprobs")


def reference_first_logprobs(setup: Setup, params, feeds) -> list:
    """(V,) float32 log-softmax of the first generated token per feed,
    from a float32 copy of the model at highest matmul precision — the
    referee for near-ties between the bf16 builds."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import build_model
    from repro.serve.engine import prompt_bucket
    cfg32 = dataclasses.replace(setup.cfg, dtype="float32",
                                kv_cache_dtype="float32")
    model = build_model(cfg32, None)
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    fwd = jax.jit(lambda p, b: model.prefill_at(p, b, backend="xla")[0])
    out = []
    with jax.default_matmul_precision("highest"):
        for feed in feeds:
            sb = prompt_bucket(len(feed), setup.min_bucket)
            toks = np.zeros((1, sb), np.int32)
            toks[0, :len(feed)] = feed
            logits = fwd(p32, {"tokens": jnp.asarray(toks),
                               "length": jnp.full((1,), len(feed),
                                                  jnp.int32)})
            out.append(np.asarray(
                jax.nn.log_softmax(logits[0, -1].astype(jnp.float32))))
    return out


def compare_host_accel(setup: Setup, params, feeds, host, accel) -> dict:
    """First-token agreement (near-ties excused), greedy match fraction
    and the largest first-token logprob gap between the two builds."""
    ref = reference_first_logprobs(setup, params, feeds)
    n_pos = n_same = 0
    disagree, ties = [], []
    gaps = []
    for i, (h, a, lp) in enumerate(zip(host, accel, ref)):
        th, ta = int(h.tokens[0]), int(a.tokens[0])
        gaps.append(abs(float(h.logprobs[0]) - float(a.logprobs[0]))
                    if th == ta else float("nan"))
        n = min(h.n_tokens, a.n_tokens)
        n_pos += n
        n_same += int((h.tokens[:n] == a.tokens[:n]).sum())
        if th != ta:
            gap = abs(float(lp[th]) - float(lp[ta]))
            (ties if gap <= NEAR_TIE_NATS else disagree).append((i, gap))
        log(f"  req {i} len {len(feeds[i])}: first host={th} accel={ta} "
            f"ref_argmax={int(lp.argmax())} host_lp={h.logprobs[0]:.5f} "
            f"accel_lp={a.logprobs[0]:.5f} ref_lp_host_tok={lp[th]:.5f} "
            f"identical_tokens={bool(np.array_equal(h.tokens, a.tokens))}")
    real = [g for g in gaps if g == g]
    stats = {"first_token_agree": sum(1 for g in gaps if g == g),
             "requests": len(feeds), "near_ties": ties,
             "greedy_match_fraction": n_same / max(n_pos, 1),
             "max_first_logprob_gap": max(real) if real else None}
    log(f"host-vs-accel: {stats}")
    if disagree:
        raise RuntimeError(
            f"first token differs HOST vs ACCEL beyond the {NEAR_TIE_NATS} "
            f"nat near-tie tolerance: {disagree}")
    return stats


def run_one_chip(setup: Setup) -> dict:
    import jax
    from repro.core.policy import PinAccel, PinHost
    from repro.models.model import build_model

    cfg = setup.cfg
    params = build_model(cfg, None).init(jax.random.PRNGKey(SEED))
    waves = main_waves(setup)
    feeds = [p for w in waves for p in w]
    result = {}

    outs = {}
    for tag, policy in (("host", PinHost()), ("accel", PinAccel()),
                        ("flip", FlipSchedule(setup.flip_at))):
        t0 = time.perf_counter()
        eng, rt = make_engine(setup, params, policy)
        t_build = time.perf_counter() - t0
        t0 = time.perf_counter()
        outs[tag] = serve(eng, waves, setup.new_tokens)
        t_serve = time.perf_counter() - t0
        log(f"[{tag}] engine build (compiles) {t_build:.2f}s, "
            f"serve {t_serve:.2f}s, prefix hits "
            f"{eng.prefix_stats()['prefix_hit_tokens']} tokens")
        report_runtime(tag, rt)
        check_tokens(tag, outs[tag], cfg.vocab_size)
        got = calls(rt)
        if tag == "accel":
            require_accel_calls(tag, rt, ("decode", "prefill_ctx"))
        if tag == "flip":
            dec = got.get("decode", {})
            if dec.get("accel", 0) < 1 or dec.get("host", 0) < 1:
                raise RuntimeError(f"[flip] decode never flipped: {dec}")
            mig = rt.summary()["per_function"]["cb_decode"]["migrations"]
            if mig < 2:
                raise RuntimeError(f"[flip] expected HOST->ACCEL->HOST, "
                                   f"got {mig} migrations")
        if eng.prefix_stats()["prefix_hit_tokens"] < setup.shared:
            raise RuntimeError(f"[{tag}] the shared prefix was not hit")
        result[tag] = got
        del eng, rt
        gc.collect()

    result["host_vs_accel"] = compare_host_accel(
        setup, params, feeds, outs["host"], outs["accel"])
    flip_same = sum(int(np.array_equal(h.tokens, f.tokens))
                    for h, f in zip(outs["host"], outs["flip"]))
    log(f"flip vs host: {flip_same}/{len(feeds)} requests identical")

    # speculative decoding: draft chain + multi-token verify on ACCEL
    eng, rt = make_engine(setup, params, PinAccel(), spec_decode=True)
    spec_outs = serve(eng, [prompts(setup, setup.spec_lens, SEED + 2)],
                      setup.new_tokens)
    report_runtime("spec", rt)
    check_tokens("spec", spec_outs, cfg.vocab_size)
    require_accel_calls("spec", rt, ("draft", "verify"))
    log(f"[spec] {eng.spec_stats()}")
    result["spec"] = calls(rt)
    del eng, rt
    gc.collect()

    # int8 KV pool: the dequantising decode and prefill-at-offset kernels
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    eng, rt = make_engine(setup, params, PinAccel(), cfg=cfg8,
                          allow_lossy_prefix_cache=True)
    i8_outs = serve(eng, [prompts(setup, setup.int8_lens, SEED + 3)],
                    setup.new_tokens)
    report_runtime("int8", rt)
    check_tokens("int8", i8_outs, cfg.vocab_size)
    require_accel_calls("int8", rt, ("decode", "prefill_ctx"))
    result["int8"] = calls(rt)
    del eng, rt
    gc.collect()
    return result


def run_replicas(setup: Setup, n: int) -> dict:
    """``n`` cluster replicas, one per device, each serving the same
    requests, against one engine serving them alone."""
    import jax
    from repro.core.policy import PinAccel
    from repro.models.model import build_model
    from repro.serve import (ClusterFrontEnd, GenerationRequest,
                             SamplingParams)

    cfg = setup.cfg
    params = build_model(cfg, None).init(jax.random.PRNGKey(SEED))
    feeds = main_waves(setup)[0]

    t0 = time.perf_counter()
    eng, rt = make_engine(setup, params, PinAccel())
    want = serve(eng, [feeds], setup.new_tokens)
    log(f"[one-engine] {time.perf_counter() - t0:.2f}s calls={calls(rt)}")
    del eng, rt
    gc.collect()

    t0 = time.perf_counter()
    fe = ClusterFrontEnd(cfg, n_engines=n, policy=PinAccel(),
                         transport="inproc", params=params, seed=SEED,
                         max_slots=MAX_SLOTS, max_seq=setup.max_seq,
                         paged=True, block_size=BLOCK, prefix_cache=True,
                         prefill_tokens_per_step=setup.chunk,
                         min_bucket=setup.min_bucket)
    devices = [w.engine.device for w in fe.workers]
    log(f"[cluster] built {n} replicas in {time.perf_counter() - t0:.2f}s "
        f"on {devices}")
    if len(set(devices)) != n:
        raise RuntimeError(f"replicas share devices: {devices}")
    for w in fe.workers:
        placed = {d for x in jax.tree.leaves((w.engine.params,
                                             w.engine.cache))
                  for d in x.devices()}
        if placed != {w.engine.device}:
            raise RuntimeError(f"{w.worker_id} state on {placed}")
    # every replica gets the whole request list before any loop starts,
    # so each sees exactly the one-engine run's admission order
    handles = [[w.submit(GenerationRequest(
        p, max_new_tokens=setup.new_tokens,
        sampling=SamplingParams(logprobs=True))) for p in feeds]
        for w in fe.workers]
    t0 = time.perf_counter()
    with fe:
        got = [[h.result(timeout=1200) for h in hs] for hs in handles]
    log(f"[cluster] served {n} x {len(feeds)} requests in "
        f"{time.perf_counter() - t0:.2f}s")
    per_device = {}
    for w, outs in zip(fe.workers, got):
        same = [bool(np.array_equal(a.tokens, b.tokens))
                for a, b in zip(outs, want)]
        stats = w.engine.device.memory_stats() or {}
        per_device[str(w.engine.device)] = {
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "identical_requests": sum(same)}
        log(f"[{w.worker_id}] device={w.engine.device} "
            f"calls={calls(w.runtime)} identical={sum(same)}/{len(same)} "
            f"bytes_in_use={stats.get('bytes_in_use')}")
        require_accel_calls(w.worker_id, w.runtime,
                            ("decode", "prefill_ctx"))
        if not all(same):
            raise RuntimeError(f"{w.worker_id} differs from the one-engine "
                               f"run on requests "
                               f"{[i for i, s in enumerate(same) if not s]}")
    log(f"per-device memory: {json.dumps(per_device)}")
    return per_device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the four-replica cluster check only")
    args = ap.parse_args(argv)

    import jax
    from repro.configs import ARCHS
    from repro.kernels import ops
    from repro.launch import compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if ops._interpret(None):
        print(f"chip_smoke: Pallas would run in interpret mode "
              f"({ops.INTERPRET_ENV} forces it)", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    log(f"device: {dev.device_kind} x{len(devices)} "
        f"compile cache: {compile_cache.enable()}")
    setup = Setup(cfg=ARCHS[MODEL])
    t0 = time.perf_counter()
    if args.chips == 4:
        run_replicas(setup, 4)
    else:
        run_one_chip(setup)
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')} "
        f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
