"""Continuous vs. synchronous batching throughput under Poisson arrivals.

The Figure-6 scenario on real JAX serving: a multi-tenant stream of
generation requests (Poisson arrivals, ragged prompt lengths and token
budgets) served three ways on the same model and weights:

  * synchronous (static) batching — collect up to ``max_slots`` arrived
    requests, left-pad prompts to a fixed width, run the whole batch for
    the batch-max token budget, then pick up the next batch;
  * continuous batching — admit requests into KV slots the moment they
    arrive, interleave prefill with decode, evict finished slots;
  * paged continuous batching (default on; ``--no-paged`` skips it) —
    same engine
    over a shared block pool at the SAME KV memory as the dense cache,
    with twice the decode rows: short requests stop reserving full rows,
    so more of them run concurrently;
  * ACCEL-backend paged serving (``--no-accel`` skips it) — every step
    on the Pallas kernels (interpret mode on CPU runners), proving the
    ACCEL build serves real tokens;
  * forced-migration serving — the same stream through an XarTrekRuntime
    under a scripted ``FlipSchedule`` SchedulingPolicy (HOST -> ACCEL ->
    HOST at fixed decode-decision counts), so the artifact records
    per-target call counts, per-backend decode step times (the asymmetry
    the scheduling policy can exploit) and the migration count.
    ``--json`` embeds ``XarTrekRuntime.summary()`` so CI can see which
    backend actually served tokens;
  * cluster serving (``--cluster N``, default 2; 0 skips) — N engine
    workers behind ONE central scheduler (TCP transport) sharing the
    Algorithm-2 policy over AGGREGATE cross-engine LoadSignals: a
    low-load trickle decodes on HOST, then a burst drives the aggregate
    queue depth over the decode threshold and decode steps migrate to
    ACCEL — real co-tenant load balancing, per-engine tok/s and the
    aggregate migration count land in the JSON artifact
    (``floor.json`` bounds ``cluster_tok_s`` and
    ``cluster_migrations`` from below);
  * process-cluster serving (``--cluster-proc N``; 0 = default skips) —
    the SAME front-end surface over N real OS-process workers
    (``ProcClusterFrontEnd``: per-process JAX runtimes, streaming IPC
    result plane, fault-tolerant supervisor), measured MLPerf
    offline-style: a fixed greedy batch sorted by length, submitted
    closed-loop, spawn/compile/warmup strictly outside the timed
    region, against a 1-process-worker leg of the same work.  Aggregate
    tok/s and the N-vs-1 scaling ratio land in the artifact — the
    threaded cluster's GIL structurally caps that ratio; processes
    don't (``floor.json`` bounds ``cluster_proc_tok_s`` and
    ``cluster_proc_scaling`` from below);
  * sampled-decode serving — the same stream with per-request
    SamplingParams (temperature 0.8, top-k 40, per-request seeds)
    through the in-graph sampler, reporting tok/s plus per-request
    TTFT/TPOT/queue-wait percentiles from the v2 RequestOutput metrics
    (floor.json holds a tok/s floor AND a ttft_p50_s ceiling);
  * prefix-cache serving (``--prefix-zipf N`` sizes it; defaults to
    ``--n-requests``) — a Zipf-popular shared-prefix stream (K distinct
    two-block system prompts, short unique suffixes) served cache-off
    and cache-on at EQUAL KV memory: the cache-on engine must compute
    STRICTLY fewer prefill tokens (asserted) and the artifact reports
    the prefix hit rate, admitted concurrency and TTFT percentiles
    (``floor.json`` bounds ``prefix_hit_rate`` from below and
    ``prefix_ttft_p50_s`` from above);
  * int8 quantised paged KV — the SAME Zipf stream over an int8+scales
    pool given exactly the f32 pool's byte budget: block capacity at
    equal bytes (>= 1.8x, asserted), prefix hit rate, bitwise
    first-token agreement with the f32 engine (asserted; first tokens
    come from exact f32 prefill math) and the full-stream greedy match
    fraction (``floor.json`` bounds ``int8_capacity_ratio``,
    ``int8_prefix_hit_rate``, ``int8_first_token_match`` and
    ``int8_greedy_match_frac`` from below);
  * chunked prefill / disaggregation (``--disagg``) — a Zipf
    long-prompt + short-decode mix served by a mixed fleet with the
    chunk budget off and on, and by a 1 prefill + 1 decode split (KV
    spans over the TCP control plane) at equal per-worker KV memory:
    wall-clock short-request TTFT p99 for all three, the worst
    single-step decode stall under the budget, and handoff/span counts
    (``floor.json`` bounds ``disagg_tok_s`` and
    ``disagg_ttft_p99_improvement`` from below, ``decode_stall_ms``
    from above);
  * speculative decoding (``--spec``) — a decode-heavy greedy stream
    on a target whose top layers are zeroed (each zeroed layer is an
    exact residual identity, so the 1-layer shared draft computes the
    SAME function and acceptance is ~1): spec-off vs spec-on at EQUAL
    target-pool KV (per-request byte-identity asserted — the emitted
    tokens are always the target's own), then the heterogeneous split
    (draft-on-HOST / verify-on-ACCEL through an XarTrekRuntime under a
    scripted policy) with per-target draft/verify call counts from
    ``summary()`` (``floor.json`` bounds ``spec_speedup``,
    ``spec_acceptance_rate`` and ``spec_byte_identical`` from below).

Emits ``serve_cb/*`` rows; derived carries tok/s for each engine, the
continuous/synchronous throughput ratio, and the paged engine's peak
concurrent slots vs. dense (the paging headline).

    PYTHONPATH=src python -m benchmarks.bench_serve_cb

CI smoke mode (tiny stream, JSON artifact, throughput floor):

    PYTHONPATH=src python -m benchmarks.bench_serve_cb \
        --n-requests 8 --rate 40 --json bench_serve_cb.json \
        --check-floor benchmarks/floor.json
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

from benchmarks.common import emit
from repro.configs import ARCHS, reduced
from repro.core.function import FunctionRegistry
from repro.core.policy import Decision, PinAccel
from repro.core.runtime import XarTrekRuntime
from repro.core.targets import TargetKind
from repro.launch import compile_cache
from repro.models.attention import paged_kv_block_bytes
from repro.serve import (ClusterFrontEnd, ContinuousBatchingEngine,
                         GenerationRequest, ProcClusterFrontEnd,
                         SamplingParams, ServeEngine)
from repro.serve import spec as spec_lib
from repro.serve.proc import require_cpu_backend
from repro.serve.scheduler import RequestQueue, poisson_arrivals

MAX_SLOTS = 4
MAX_SEQ = 96
PAD_TO = 32            # static batching pads every prompt to this width
BLOCK_SIZE = 32        # paged engine's KV block width
SEED = 0
# forced-migration schedule: decode-decision counts at which the
# scripted policy flips HOST -> ACCEL and back (well inside even the CI
# smoke stream, whose longest request decodes ~15+ steps)
MIGRATE_AT = (4, 10)
# prefix-cache scenario: K distinct shared prefixes spanning this many
# full KV blocks each (the "same system prompt" multi-tenant shape)
N_PREFIXES = 4
PREFIX_BLOCKS = 2
# disaggregation scenario (--disagg): long "document" prompt width
# (sized so its monolithic prefill is the widest bucket the engine
# serves — the head-of-line block the scenario measures) and the
# per-step chunk budget (= the engines' min_bucket, so every chunk
# call rides the warmed compile signature)
DISAGG_LONG = 88
CHUNK_BUDGET = 8
# speculative-decoding scenario (--spec): draft length (= verify width)
# and the zeroed-target depth — keep 1 live layer so the 1-layer shared
# draft computes exactly the target's function (acceptance ~1), making
# the k-per-2-dispatches bound observable on random weights.  k=8:
# a CPU decode step is weight-streaming-bound, so an 8-token verify
# costs barely more than a 1-token step and the win is DISPATCH
# amortisation — wider k amortises further while acceptance holds
SPEC_DRAFT_LEN = 8
SPEC_KEEP_LAYERS = 1


class FlipSchedule:
    """Scripted SchedulingPolicy: decode decisions 1..at[0] on HOST,
    (at[0], at[1]] on ACCEL, HOST after — the forced
    HOST -> ACCEL -> HOST mid-stream schedule expressed through the
    policy protocol instead of the deprecated ``on_step`` hook.
    Prefills stay on HOST so the flip isolates the decode asymmetry."""

    name = "flip_schedule"

    def __init__(self, at=MIGRATE_AT):
        self.at = at
        self.decodes = 0

    def decide(self, signals, row, residency):
        if not row.app.endswith("_decode"):
            return Decision(TargetKind.HOST)
        self.decodes += 1
        if self.at[0] < self.decodes <= self.at[1] and residency.resident:
            return Decision(TargetKind.ACCEL)
        return Decision(TargetKind.HOST)


class SpecSplit:
    """Scripted SchedulingPolicy for the --spec split leg: the verify
    step runs on ACCEL (once its kernel bank is resident), the draft
    chain and everything else stay on HOST — the headline Xar-Trek
    configuration with two registered binaries busy per round."""

    name = "spec_split"

    def decide(self, signals, row, residency):
        if row.app.endswith("_verify") and residency.resident:
            return Decision(TargetKind.ACCEL)
        return Decision(TargetKind.HOST)


def make_requests(vocab: int, n: int, rate: float, seed: int = SEED,
                  sampling: bool = False) -> list[GenerationRequest]:
    """With ``sampling=True`` every request carries the sampled-decode
    spec (temperature 0.8, top-k 40) and its own seed."""
    rng = np.random.RandomState(seed)
    arrivals = poisson_arrivals(n, rate, seed)
    return [GenerationRequest(
        rng.randint(0, vocab, size=int(rng.randint(4, PAD_TO))),
        max_new_tokens=int(rng.randint(4, 24)),
        arrival_s=t,
        sampling=(SamplingParams(temperature=0.8, top_k=40, seed=1000 + i)
                  if sampling else SamplingParams()))
            for i, t in enumerate(arrivals)]


def make_prefix_requests(vocab: int, n: int, rate: float,
                         seed: int = SEED) -> list[GenerationRequest]:
    """Zipf-popular shared prefixes: ``N_PREFIXES`` distinct two-block
    (64-token) prefixes with popularity ~ 1/rank, each request
    appending a short unique suffix — the multi-tenant shared
    system-prompt stream the prefix cache exists for."""
    rng = np.random.RandomState(seed + 17)
    plen = PREFIX_BLOCKS * BLOCK_SIZE
    prefixes = [rng.randint(0, vocab, size=plen)
                for _ in range(N_PREFIXES)]
    weights = 1.0 / np.arange(1.0, N_PREFIXES + 1)
    weights /= weights.sum()
    return [GenerationRequest(
        np.concatenate([prefixes[rng.choice(N_PREFIXES, p=weights)],
                        rng.randint(0, vocab, size=int(rng.randint(4, 9)))]),
        max_new_tokens=int(rng.randint(4, 9)), arrival_s=t)
        for t in poisson_arrivals(n, rate, seed + 17)]


def total_tokens(reqs: list[GenerationRequest]) -> int:
    return sum(r.max_new_tokens for r in reqs)


def serve_static(engine: ServeEngine,
                 reqs: list[GenerationRequest]) -> float:
    """Static batching: batches of up to MAX_SLOTS arrived requests, each
    left-padded to PAD_TO and run for the batch-max token budget.  The
    batch shape is held fixed at (MAX_SLOTS, PAD_TO) so the baseline
    compiles exactly once (generous: ragged shapes would recompile)."""
    queue = RequestQueue(reqs)
    done = 0
    t0 = time.perf_counter()
    while done < len(reqs):
        now = time.perf_counter() - t0
        batch: list[GenerationRequest] = []
        while len(batch) < MAX_SLOTS:
            r = queue.pop_arrived(now)
            if r is None:
                break
            batch.append(r)
        if not batch:
            nxt = queue.next_arrival()
            time.sleep(max(min(nxt - now, 0.05), 0.001))
            continue
        toks = np.zeros((MAX_SLOTS, PAD_TO), np.int32)
        for i, req in enumerate(batch):
            toks[i, PAD_TO - req.prompt_len:] = req.prompt    # left pad
        engine.generate(toks, max_new_tokens=max(r.max_new_tokens
                                                 for r in batch))
        done += len(batch)
    return time.perf_counter() - t0


def serve_continuous(engine: ContinuousBatchingEngine,
                     reqs: list[GenerationRequest]
                     ) -> tuple[float, dict]:
    t0 = time.perf_counter()
    out = engine.run(reqs)
    elapsed = time.perf_counter() - t0
    assert len(out) == len(reqs), (len(out), len(reqs))
    return elapsed, out


def warm(engine, vocab: int, static: bool = False) -> None:
    reqs = [GenerationRequest(np.arange(1, 5, dtype=np.int32) % vocab,
                              max_new_tokens=2)]
    if static:
        serve_static(engine, reqs)
    else:
        serve_continuous(engine, reqs)
        engine.reset_stats()


def latency_percentiles(outputs: dict) -> dict:
    """Per-request latency percentiles from v2 RequestOutput metrics."""
    ttft = [o.ttft_s for o in outputs.values()]
    tpot = [o.tpot_s for o in outputs.values()]
    qw = [o.queue_wait_s for o in outputs.values()]
    return {
        "ttft_p50_s": float(np.percentile(ttft, 50)),
        "ttft_p90_s": float(np.percentile(ttft, 90)),
        "ttft_p99_s": float(np.percentile(ttft, 99)),
        "tpot_p50_s": float(np.percentile(tpot, 50)),
        "tpot_p90_s": float(np.percentile(tpot, 90)),
        "queue_wait_p50_s": float(np.percentile(qw, 50)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n-requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=12.0,
                    help="Poisson arrival rate (requests/s)")
    ap.add_argument("--seed", type=int, default=SEED)
    ap.add_argument("--no-paged", action="store_true",
                    help="skip the paged-engine run")
    ap.add_argument("--no-accel", action="store_true",
                    help="skip the ACCEL-backend and forced-migration runs")
    ap.add_argument("--prefix-zipf", type=int, default=0, metavar="N",
                    help="requests in the shared-prefix Zipf scenario "
                         "(default: --n-requests)")
    ap.add_argument("--cluster", type=int, default=2, metavar="N",
                    help="run N engine workers behind one TCP scheduler "
                         "(0 skips; --no-accel also skips it — the "
                         "cluster migrates steps to the Pallas build)")
    ap.add_argument("--cluster-proc", type=int, default=0, metavar="N",
                    help="run the process-cluster scenario: N OS-process "
                         "engine workers vs a 1-process leg on the same "
                         "offline batch (0 skips — each worker spawns "
                         "its own JAX runtime)")
    ap.add_argument("--disagg", action="store_true",
                    help="run the chunked-prefill / disaggregation "
                         "scenario: a Zipf long-prompt + short-decode "
                         "mix served by a mixed fleet (chunking off and "
                         "on) and by a 1 prefill + 1 decode split at "
                         "equal KV memory")
    ap.add_argument("--spec", action="store_true",
                    help="run the speculative-decoding scenario: a "
                         "decode-heavy greedy stream spec-off vs "
                         "spec-on at equal target-pool KV, plus the "
                         "draft-on-HOST / verify-on-ACCEL split "
                         "through a runtime")
    ap.add_argument("--json", metavar="PATH",
                    help="write results as JSON (CI artifact)")
    ap.add_argument("--check-floor", metavar="PATH",
                    help="fail (exit 1) if tok/s drops below the floors "
                         "in this JSON file")
    # parse_known_args: benchmarks.run invokes main() under ITS argv
    # (--only ...); unknown flags must not crash the module
    args, _ = ap.parse_known_args(argv)
    if args.cluster_proc:
        # refuse before the in-process legs below take the chip
        require_cpu_backend()

    cfg = dataclasses.replace(reduced(ARCHS["smollm-135m"]), dtype="float32")
    sync = ServeEngine(cfg, seed=args.seed)
    cb = ContinuousBatchingEngine(cfg, max_slots=MAX_SLOTS, max_seq=MAX_SEQ,
                                  params=sync.params)
    # equal usable KV memory to the dense engine (MAX_SLOTS * MAX_SEQ
    # positions), but 2x the decode rows: the pool, not row count, is
    # the capacity bound, so the ragged stream packs more requests in
    paged = None
    if not args.no_paged:
        paged = ContinuousBatchingEngine(
            cfg, max_slots=2 * MAX_SLOTS, max_seq=MAX_SEQ,
            params=sync.params, paged=True, block_size=BLOCK_SIZE,
            num_blocks=MAX_SLOTS * MAX_SEQ // BLOCK_SIZE, fn_prefix="pcb")

    # warm every compile cache outside the timed runs
    warm(sync, cfg.vocab_size, static=True)
    warm(cb, cfg.vocab_size)
    if paged is not None:
        warm(paged, cfg.vocab_size)

    reqs = make_requests(cfg.vocab_size, args.n_requests, args.rate,
                         args.seed)
    tokens = total_tokens(reqs)

    t_sync = serve_static(sync, [dataclasses.replace(r) for r in reqs])
    t_cb, _ = serve_continuous(cb, [dataclasses.replace(r) for r in reqs])
    results = {
        "n_requests": args.n_requests, "rate_per_s": args.rate,
        "tokens": tokens,
        "sync_tok_s": tokens / t_sync,
        "cb_tok_s": tokens / t_cb,
        "cb_peak_active": cb.slots.stats["peak_active"],
        "cb_vs_sync": (tokens / t_cb) / max(tokens / t_sync, 1e-9),
    }
    if paged is not None:
        t_paged, _ = serve_continuous(paged,
                                      [dataclasses.replace(r) for r in reqs])
        results.update({
            "paged_tok_s": tokens / t_paged,
            "paged_peak_active": paged.slots.stats["peak_active"],
            "paged_preempted": paged.slots.stats["preempted"],
            "paged_vs_dense_cb": (tokens / t_paged) / (tokens / t_cb),
        })

    # sampled decode (temperature 0.8, top-k 40, per-request seeds)
    # through the in-graph sampler, on the ALREADY-WARM paged engine:
    # the greedy run above populated every prefill shape bucket, so this
    # measures steady-state serving — sampling adds no recompiles (the
    # (B,) sampling vectors are data, not shapes) and the TTFT/TPOT
    # percentiles reflect serving latency, not compile noise
    sampled_engine = paged if paged is not None else cb
    sreqs = make_requests(cfg.vocab_size, args.n_requests, args.rate,
                          args.seed, sampling=True)
    t_sampled, souts = serve_continuous(sampled_engine, sreqs)
    results["sampled_cb_tok_s"] = tokens / t_sampled
    results.update(latency_percentiles(souts))

    # prefix caching: the SAME Zipf shared-prefix stream served
    # cache-off then cache-on at equal KV memory (same pool, same
    # rows).  Warm prompts share no scenario prefix, so the measured
    # runs start from a cold index; the cache-on engine must compute
    # strictly fewer prefill tokens — the matched spans — or the
    # feature is not doing its one job
    n_prefix = args.prefix_zipf or args.n_requests
    preqs = make_prefix_requests(cfg.vocab_size, n_prefix, args.rate,
                                 args.seed)
    ptokens = total_tokens(preqs)
    # prefix engines pin kv_cache_dtype=float32: the engine refuses a
    # prefix cache over a lossy pool (f32 compute over the default bf16
    # pool rounds on write), and f32 is the equal-bytes baseline the
    # int8 run below is measured against
    pcfg = dataclasses.replace(cfg, kv_cache_dtype="float32")
    n_pblocks = MAX_SLOTS * MAX_SEQ // BLOCK_SIZE
    pkw = dict(max_slots=2 * MAX_SLOTS, max_seq=MAX_SEQ,
               params=sync.params, paged=True, block_size=BLOCK_SIZE,
               num_blocks=n_pblocks)
    pfx_off = ContinuousBatchingEngine(pcfg, fn_prefix="pfo", **pkw)
    pfx_on = ContinuousBatchingEngine(pcfg, fn_prefix="pfx",
                                      prefix_cache=True, **pkw)
    warm(pfx_off, cfg.vocab_size)
    warm(pfx_on, cfg.vocab_size)
    t_pfx_off, _ = serve_continuous(pfx_off, [dataclasses.replace(r)
                                              for r in preqs])
    t_pfx_on, pouts = serve_continuous(pfx_on, [dataclasses.replace(r)
                                                for r in preqs])
    on_stats, off_stats = pfx_on.prefix_stats(), pfx_off.prefix_stats()
    assert on_stats["prefill_tokens"] < off_stats["prefill_tokens"], (
        "prefix cache computed as many prefill tokens as cache-off",
        on_stats, off_stats)
    pttft = sorted(o.ttft_s for o in pouts.values())
    results.update({
        "prefix_n_requests": n_prefix,
        "prefix_off_tok_s": ptokens / t_pfx_off,
        "prefix_on_tok_s": ptokens / t_pfx_on,
        "prefix_hit_rate": on_stats["prefix_hit_rate"],
        "prefix_hit_tokens": on_stats["prefix_hit_tokens"],
        "prefix_prefill_tokens_on": on_stats["prefill_tokens"],
        "prefix_prefill_tokens_off": off_stats["prefill_tokens"],
        "prefix_cow_forks": on_stats["cow_forks"],
        "prefix_peak_active_on": pfx_on.slots.stats["peak_active"],
        "prefix_peak_active_off": pfx_off.slots.stats["peak_active"],
        "prefix_ttft_p50_s": pttft[len(pttft) // 2],
        "prefix_ttft_p90_s": pttft[int(len(pttft) * 0.9)
                                   if len(pttft) > 1 else 0],
    })

    # int8 quantised pool at EQUAL KV BYTES: the same Zipf stream over
    # an int8+scales pool given exactly the f32 pool's byte budget.
    # The capacity win (>= 1.8x blocks) and the prefix hit rate must
    # hold TOGETHER — more blocks are worthless if quantisation broke
    # block-hash reuse.  Tolerance story: each request's first token
    # comes from exact f32 prefill math and must match the f32 engine
    # bitwise; deeper tokens may flip where the random-init model's
    # top-2 logit margin is below the int8 perturbation, so the full
    # stream gets a match-fraction floor rather than an equality check.
    f32_bytes = paged_kv_block_bytes(BLOCK_SIZE, cfg.num_kv_heads,
                                     cfg.resolved_head_dim, "float32")
    i8_bytes = paged_kv_block_bytes(BLOCK_SIZE, cfg.num_kv_heads,
                                    cfg.resolved_head_dim, "int8")
    n_i8 = int(n_pblocks * f32_bytes) // i8_bytes
    icfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    pfx_i8 = ContinuousBatchingEngine(
        icfg, fn_prefix="pfi", prefix_cache=True,
        allow_lossy_prefix_cache=True,
        **dict(pkw, num_blocks=n_i8))
    warm(pfx_i8, cfg.vocab_size)
    t_pfx_i8, iouts = serve_continuous(pfx_i8, [dataclasses.replace(r)
                                                for r in preqs])
    i8_stats = pfx_i8.prefix_stats()
    cap_ratio = n_i8 / n_pblocks
    assert cap_ratio >= 1.8, (n_i8, n_pblocks)
    firsts = [int(pouts[r].tokens[0]) == int(iouts[r].tokens[0])
              for r in pouts]
    assert all(firsts), "int8 first tokens diverged from f32 prefill"
    matched = total = 0
    for r in pouts:
        a, b = pouts[r].tokens, iouts[r].tokens
        n = min(len(a), len(b))
        matched += int((a[:n] == b[:n]).sum())
        total += n
    results.update({
        "int8_capacity_ratio": cap_ratio,
        "int8_num_blocks": n_i8,
        "int8_prefix_hit_rate": i8_stats["prefix_hit_rate"],
        "int8_on_tok_s": ptokens / t_pfx_i8,
        "int8_first_token_match": sum(firsts) / len(firsts),
        "int8_greedy_match_frac": matched / total,
    })

    t_accel = t_mig = None
    if not args.no_accel:
        # every step on the Pallas kernels (interpret mode on CPU)
        accel = ContinuousBatchingEngine(
            cfg, max_slots=2 * MAX_SLOTS, max_seq=MAX_SEQ,
            params=sync.params, paged=True, block_size=BLOCK_SIZE,
            num_blocks=MAX_SLOTS * MAX_SEQ // BLOCK_SIZE, fn_prefix="acb",
            policy=PinAccel())
        warm(accel, cfg.vocab_size)
        t_accel, _ = serve_continuous(accel,
                                      [dataclasses.replace(r) for r in reqs])
        results["accel_cb_tok_s"] = tokens / t_accel

        # forced HOST -> ACCEL -> HOST schedule through the runtime via
        # the scripted FlipSchedule policy, flipped mid-stream while
        # slots are live: the policy's target choice is a real kernel
        # swap
        rt = XarTrekRuntime(registry=FunctionRegistry(),
                            policy="always_host")
        mig = ContinuousBatchingEngine(
            cfg, max_slots=2 * MAX_SLOTS, max_seq=MAX_SEQ,
            params=sync.params, paged=True, block_size=BLOCK_SIZE,
            num_blocks=MAX_SLOTS * MAX_SEQ // BLOCK_SIZE, fn_prefix="mig",
            runtime=rt)
        warm(mig, cfg.vocab_size)
        rt.call_log.clear()                   # timed region only
        rt.server.policy = FlipSchedule()     # warm steps don't count
        t_mig, _ = serve_continuous(mig, [dataclasses.replace(r)
                                          for r in reqs])
        summary = rt.summary()
        decode_fn = summary["per_function"]["mig_decode"]
        step_ms = {"host": [], "accel": []}
        for rec in rt.call_log:
            if rec["fn"] == "mig_decode":
                step_ms[rec["target"]].append(rec["ms"])
        results.update({
            "mig_tok_s": tokens / t_mig,
            "mig_host_decode_calls": decode_fn["calls"].get("host", 0),
            "mig_accel_decode_calls": decode_fn["calls"].get("accel", 0),
            "mig_migrations": decode_fn["migrations"],
            # per-backend decode step time: the perf asymmetry the
            # scheduling policy can exploit (Fig. 6's lever)
            "mig_host_decode_ms": float(np.mean(step_ms["host"]))
            if step_ms["host"] else None,
            "mig_accel_decode_ms": float(np.mean(step_ms["accel"]))
            if step_ms["accel"] else None,
            "runtime_summary": summary,
        })

    # N engines, one TCP scheduler, shared Algorithm-2 policy over
    # aggregate signals: a low-load trickle decodes on HOST, the burst's
    # queue pressure crosses the decode threshold, steps migrate to
    # ACCEL — the ROADMAP's co-tenant balancing, measured
    t_cluster = None
    if args.cluster and not args.no_accel:
        fe = ClusterFrontEnd(cfg, n_engines=args.cluster, policy="xartrek",
                             transport="tcp", params=sync.params,
                             max_slots=MAX_SLOTS, max_seq=MAX_SEQ,
                             worker_prefix="cw")
        fe.set_decode_thresholds(fpga_thr=3.0)
        crng = np.random.RandomState(args.seed)

        def short_req(n_new):
            # prompts fit the warmed 8-wide bucket: no mid-scenario
            # shape-bucket compile can eat the pressure window
            return GenerationRequest(
                crng.randint(0, cfg.vocab_size,
                             size=int(crng.randint(4, 9))),
                max_new_tokens=n_new)

        with fe:
            fe.warmup()
            t0 = time.perf_counter()
            trickle = [fe.submit(short_req(40))]      # low load -> HOST
            time.sleep(0.02)
            burst = [fe.submit(short_req(8))          # pressure -> ACCEL
                     for _ in range(max(args.n_requests,
                                        4 * args.cluster))]
            outs = fe.drain()
            t_cluster = time.perf_counter() - t0
            csummary = fe.summary()
        assert len(outs) == len(trickle) + len(burst)
        ctokens = sum(o.n_tokens for o in outs.values())
        per_engine = {}
        for w in fe.workers:
            wtok = sum(o.n_tokens for rid, o in outs.items()
                       if fe.last_owners.get(rid) == w.worker_id)
            decode = (csummary["per_engine"][w.worker_id]["per_function"]
                      .get(f"{w.worker_id}_decode", {}))
            per_engine[w.worker_id] = {
                "tok_s": wtok / t_cluster,
                "decode_calls": decode.get("calls", {}),
                "migrations": decode.get("migrations", 0),
            }
        results.update({
            "cluster_n": args.cluster,
            "cluster_tok_s": ctokens / t_cluster,
            "cluster_migrations": csummary["migrations"],
            "cluster_decisions": csummary["decisions"],
            "cluster_per_engine": per_engine,
        })

    # process-cluster scaling, MLPerf offline style: a FIXED greedy
    # batch, sorted longest-first (the offline scenario's length-sorted
    # batching), submitted closed-loop to N OS-process workers and to a
    # single-process-worker leg of the exact same work.  Spawn, engine
    # compile and warmup (including the longest prompt bucket) are
    # strictly outside the timed region.  The threaded cluster shares
    # one GIL, so its N-worker aggregate is structurally capped near
    # 1x; real processes are the honest version of the scaling claim.
    t_cproc = None
    if args.cluster_proc:
        prng = np.random.RandomState(args.seed + 13)
        n_p = max(args.n_requests, 8 * args.cluster_proc)
        proc_reqs = sorted(
            (GenerationRequest(
                prng.randint(0, cfg.vocab_size,
                             size=int(prng.randint(4, PAD_TO))),
                max_new_tokens=32,
                sampling=SamplingParams(temperature=0.0))
             for _ in range(n_p)),
            key=lambda r: r.prompt_len + r.max_new_tokens, reverse=True)
        ptok = total_tokens(proc_reqs)

        def proc_leg(n_workers: int) -> tuple[float, dict]:
            with ProcClusterFrontEnd(
                    cfg, n_workers=n_workers, policy="xartrek",
                    seed=args.seed, max_slots=MAX_SLOTS, max_seq=MAX_SEQ,
                    worker_prefix=f"pw{n_workers}_") as fe:
                fe.warmup(max_prompt=PAD_TO - 1)
                t0 = time.perf_counter()
                for r in proc_reqs:
                    fe.submit(dataclasses.replace(r))
                outs = fe.drain()
                elapsed = time.perf_counter() - t0
                summ = fe.summary()
            assert len(outs) == n_p
            assert sum(o.n_tokens for o in outs.values()) == ptok
            return elapsed, summ

        t_cproc1, _ = proc_leg(1)
        t_cproc, cproc_summ = proc_leg(args.cluster_proc)
        try:
            usable_cores = len(os.sched_getaffinity(0))
        except AttributeError:          # non-Linux
            usable_cores = os.cpu_count() or 1
        results.update({
            "cluster_proc_n": args.cluster_proc,
            "cluster_proc_cores": usable_cores,
            "cluster_proc_tok_s": ptok / t_cproc,
            "cluster_proc_1w_tok_s": ptok / t_cproc1,
            "cluster_proc_scaling": t_cproc1 / t_cproc,
            "cluster_proc_failures": cproc_summ["failures"],
            "cluster_proc_heartbeats": {
                wid: w["heartbeats"]
                for wid, w in cproc_summ["workers"].items()},
        })

    # chunked prefill + prefill/decode disaggregation: an adversarial
    # Zipf long-prompt / short-decode mix served three ways at EQUAL
    # per-worker KV memory — a mixed fleet with chunking off (the
    # baseline whose monolithic long prefills stall co-resident
    # decodes), the same fleet with the chunk budget on (bounded
    # per-step stall, measured), and a 1 prefill + 1 decode split
    # (spans over the TCP control plane; the interactive class never
    # shares an engine with a long prefill).  TTFT is wall-clock
    # submit -> first streamed token, so the disaggregated path pays
    # for its own serialization and handoff in the number it reports.
    t_disagg = None
    if args.disagg:
        # the scenario sets its own pressure: arrivals must outpace a
        # monolithic long prefill or least-loaded routing dodges every
        # head-of-line block and the baseline measures nothing
        n_d = max(args.n_requests, 18)
        d_rate = max(args.rate, 80.0)
        drng = np.random.RandomState(args.seed + 7)
        specs = []                      # (prompt, n_new, is_long)
        for i in range(n_d):
            if i % 3 < 2:               # longs arrive in pairs: one per
                # mixed worker, so the following short finds BOTH
                # workers mid-prefill and least-loaded routing can't
                # dodge the head-of-line block
                specs.append((drng.randint(0, cfg.vocab_size,
                                           size=DISAGG_LONG), 4, True))
            else:                       # interactive short, Zipf decode
                n_new = int(4 + min(drng.zipf(2.0) * 4, 16))
                specs.append((drng.randint(0, cfg.vocab_size,
                                           size=int(drng.randint(4, 9))),
                              n_new, False))
        d_arrivals = poisson_arrivals(n_d, d_rate, args.seed + 7)
        n_dblocks = MAX_SLOTS * MAX_SEQ // BLOCK_SIZE

        def disagg_leg(roles=None, chunk=None, transport="inproc",
                       prefix="dg"):
            kw = dict(paged=True, block_size=BLOCK_SIZE,
                      num_blocks=n_dblocks)
            if roles is not None:
                kw["roles"] = roles
            if chunk is not None:
                kw["prefill_tokens_per_step"] = chunk
            fe = ClusterFrontEnd(cfg, n_engines=2, policy="xartrek",
                                 transport=transport, params=sync.params,
                                 max_slots=MAX_SLOTS, max_seq=MAX_SEQ,
                                 worker_prefix=prefix, **kw)
            ttft, rids = {}, []
            with fe:
                fe.warmup()
                # compile the long-prompt path (monolithic bucket or
                # span tier) outside the measured window — on EVERY
                # decode-capable worker, not just the least-loaded one
                long_warm = np.arange(1, DISAGG_LONG + 1,
                                      dtype=np.int32) % cfg.vocab_size
                if roles is None:
                    warm_h = [w.submit(GenerationRequest(
                        long_warm, max_new_tokens=2))
                        for w in fe.workers]
                    for h in warm_h:
                        h.result(timeout=120)
                else:
                    fe.submit(GenerationRequest(long_warm,
                                                max_new_tokens=2))
                    fe.drain()
                for w in fe.workers:
                    w.engine.reset_stats()
                t0 = time.perf_counter()
                for (prompt, n_new, _), arr in zip(specs, d_arrivals):
                    now = time.perf_counter() - t0
                    if arr > now:
                        time.sleep(arr - now)
                    req = GenerationRequest(prompt, max_new_tokens=n_new)
                    sub = time.perf_counter()

                    def cb(_tok, rid=req.req_id, sub=sub):
                        ttft.setdefault(rid, time.perf_counter() - sub)
                    rids.append(req.req_id)
                    fe.submit(req, on_token=cb)
                outs = fe.drain()
                elapsed = time.perf_counter() - t0
                summ = fe.summary()
            tok = sum(o.n_tokens for o in outs.values())
            short = [ttft[rid] for rid, (_, _, is_long)
                     in zip(rids, specs) if not is_long]
            return (tok, elapsed,
                    float(np.percentile(short, 99)), summ)

        _, _, base_p99, _ = disagg_leg(prefix="db")
        chk_tok, chk_t, chk_p99, chk_summ = disagg_leg(
            chunk=CHUNK_BUDGET, prefix="dc")
        dtokens, t_disagg, dis_p99, dis_summ = disagg_leg(
            roles=("prefill", "decode"), chunk=CHUNK_BUDGET,
            transport="tcp", prefix="dd")
        chunked = chk_summ["chunked_prefill"].values()
        results.update({
            "disagg_baseline_ttft_p99_s": base_p99,
            "chunked_mixed_tok_s": chk_tok / chk_t,
            "chunked_mixed_ttft_p99_s": chk_p99,
            # worst single-step decode stall under the chunk budget —
            # the SLO number floor.json holds a ceiling on
            "decode_stall_ms": max(
                v["decode_stall_max_ms"] for v in chunked),
            "decode_stall_total_ms": sum(
                v["decode_stall_ms"] for v in chunked),
            "disagg_tok_s": dtokens / t_disagg,
            "disagg_ttft_p99_s": dis_p99,
            "disagg_ttft_p99_improvement": base_p99 / max(dis_p99, 1e-9),
            "disagg_handoffs": dis_summ["handoffs"],
            "disagg_spans": sum(
                v["spans_admitted"]
                for v in dis_summ["chunked_prefill"].values()),
        })

    # speculative decoding: spec-off vs spec-on at EQUAL target-pool KV
    # on a zeroed-top-layers target (the 1-layer shared draft then
    # computes exactly the target's function, so acceptance ~1 and the
    # k-per-2-dispatches bound is observable), then the heterogeneous
    # draft-on-HOST / verify-on-ACCEL split through a runtime.  The
    # stream is decode-heavy (short prompts, long budgets): that is the
    # regime speculation exists for, and the one the floor bounds.
    t_spec = None
    if args.spec:
        zp = spec_lib.zero_top_layers(sync.params, SPEC_KEEP_LAYERS)
        n_s = max(args.n_requests, 8)

        # closed-loop (all requests pre-arrived): an open-loop trickle
        # at smoke rates is arrival-bound, and a speedup of decode
        # dispatches can't show up in time spent WAITING for arrivals.
        # Decode-heavy (short prompts, 64 new tokens) so the measured
        # ratio is the decode-path speedup, not prefill dilution.
        def spec_reqs():
            rng = np.random.RandomState(args.seed + 11)
            return [GenerationRequest(
                rng.randint(0, cfg.vocab_size, size=int(rng.randint(4, 9))),
                max_new_tokens=64, arrival_s=0.0)
                for _ in range(n_s)]

        stok = total_tokens(spec_reqs())
        # lossless f32 target pool (same for EVERY leg): the zeroed-top
        # construction makes draft == target exactly, but the default
        # bf16 pool rounds KV on write where the dense f32 draft cache
        # doesn't — occasional argmax flips that cap acceptance ~0.94
        # and are noise in THIS scenario's k-per-2-dispatches bound
        # (acceptance is exactly 1.0 on a lossless pool)
        scfg = dataclasses.replace(cfg, kv_cache_dtype="float32")
        skw = dict(max_slots=MAX_SLOTS, max_seq=MAX_SEQ, params=zp,
                   paged=True, block_size=BLOCK_SIZE,
                   num_blocks=MAX_SLOTS * MAX_SEQ // BLOCK_SIZE)
        spkw = dict(spec_decode=True, spec_draft_len=SPEC_DRAFT_LEN,
                    spec_draft_layers=SPEC_KEEP_LAYERS)
        s_off = ContinuousBatchingEngine(scfg, fn_prefix="sb", **skw)
        s_on = ContinuousBatchingEngine(scfg, fn_prefix="ss", **spkw, **skw)
        warm(s_off, cfg.vocab_size)
        warm(s_on, cfg.vocab_size)
        # best-of-3, legs interleaved: the floored number is a RATIO of
        # two short wall-clock runs, so one co-tenant scheduling blip on
        # a shared CI runner can skew a single pair; the fastest rep of
        # each leg is the least-interfered measurement of the same
        # fixed work (identical token streams every rep — asserted)
        t_s_off, t_spec, off_outs, identical = np.inf, np.inf, None, True
        for _ in range(3):
            off_reqs, on_reqs = spec_reqs(), spec_reqs()
            t_off_i, off_outs = serve_continuous(s_off, off_reqs)
            t_on_i, on_outs = serve_continuous(s_on, on_reqs)
            t_s_off, t_spec = min(t_s_off, t_off_i), min(t_spec, t_on_i)
            identical = identical and all(
                np.array_equal(off_outs[a.req_id].tokens,
                               on_outs[b.req_id].tokens)
                for a, b in zip(off_reqs, on_reqs))
        sstats = s_on.spec_stats()

        # split leg: draft and verify registered as DISTINCT binaries,
        # dispatched to different targets by the scripted policy
        s_rt = XarTrekRuntime(registry=FunctionRegistry(),
                              policy="always_host")
        s_split = ContinuousBatchingEngine(scfg, fn_prefix="sx",
                                           runtime=s_rt, **spkw, **skw)
        s_rt.server.policy = SpecSplit()
        warm(s_split, cfg.vocab_size)
        split_reqs = spec_reqs()
        t_s_split, split_outs = serve_continuous(s_split, split_reqs)
        identical = identical and all(
            np.array_equal(off_outs[a.req_id].tokens,
                           split_outs[b.req_id].tokens)
            for a, b in zip(off_reqs, split_reqs))
        spf = s_rt.summary()["per_function"]
        results.update({
            "spec_off_tok_s": stok / t_s_off,
            "spec_on_tok_s": stok / t_spec,
            "spec_speedup": t_s_off / t_spec,
            "spec_acceptance_rate": sstats["spec_acceptance_rate"],
            "spec_rounds": sstats["spec_rounds"],
            "spec_emitted_tokens": sstats["spec_emitted_tokens"],
            "spec_byte_identical": 1.0 if identical else 0.0,
            "spec_split_tok_s": stok / t_s_split,
            "spec_draft_calls_host":
                spf["sx_draft"]["calls"].get("host", 0),
            "spec_draft_calls_accel":
                spf["sx_draft"]["calls"].get("accel", 0),
            "spec_verify_calls_host":
                spf["sx_verify"]["calls"].get("host", 0),
            "spec_verify_calls_accel":
                spf["sx_verify"]["calls"].get("accel", 0),
        })

    util = cb.stats["decode_row_util"] / max(cb.stats["decode_steps"], 1)
    emit("serve_cb/sync", t_sync * 1e6 / tokens,
         f"{results['sync_tok_s']:.1f}tok/s")
    emit("serve_cb/continuous", t_cb * 1e6 / tokens,
         f"{results['cb_tok_s']:.1f}tok/s util={util:.2f}")
    emit("serve_cb/ratio", 0.0,
         f"continuous_vs_sync={results['cb_vs_sync']:.2f}x")
    if paged is not None:
        emit("serve_cb/paged", t_paged * 1e6 / tokens,
             f"{results['paged_tok_s']:.1f}tok/s "
             f"peak_slots={results['paged_peak_active']}"
             f"(dense={results['cb_peak_active']}) "
             f"preempted={results['paged_preempted']}")
    emit("serve_cb/sampled", t_sampled * 1e6 / tokens,
         f"{results['sampled_cb_tok_s']:.1f}tok/s t=0.8 k=40 "
         f"ttft_p50={results['ttft_p50_s'] * 1e3:.0f}ms "
         f"tpot_p50={results['tpot_p50_s'] * 1e3:.1f}ms")
    emit("serve_cb/prefix", t_pfx_on * 1e6 / ptokens,
         f"{results['prefix_on_tok_s']:.1f}tok/s "
         f"hit_rate={results['prefix_hit_rate']:.2f} "
         f"prefill={results['prefix_prefill_tokens_on']}"
         f"(off={results['prefix_prefill_tokens_off']}) "
         f"peak_slots={results['prefix_peak_active_on']}"
         f"(off={results['prefix_peak_active_off']}) "
         f"cow={results['prefix_cow_forks']} "
         f"ttft_p50={results['prefix_ttft_p50_s'] * 1e3:.0f}ms")
    emit("serve_cb/prefix_int8", t_pfx_i8 * 1e6 / ptokens,
         f"{results['int8_on_tok_s']:.1f}tok/s "
         f"capacity={results['int8_capacity_ratio']:.2f}x "
         f"({results['int8_num_blocks']}blk vs {n_pblocks}) "
         f"hit_rate={results['int8_prefix_hit_rate']:.2f} "
         f"first_tok_match={results['int8_first_token_match']:.2f} "
         f"greedy_match={results['int8_greedy_match_frac']:.2f}")
    if t_accel is not None:
        emit("serve_cb/accel", t_accel * 1e6 / tokens,
             f"{results['accel_cb_tok_s']:.1f}tok/s pallas")
        hd_ms = results["mig_host_decode_ms"]
        ad_ms = results["mig_accel_decode_ms"]
        emit("serve_cb/migration", t_mig * 1e6 / tokens,
             f"{results['mig_tok_s']:.1f}tok/s "
             f"host={results['mig_host_decode_calls']}x"
             f"{'' if hd_ms is None else f'{hd_ms:.1f}ms'} "
             f"accel={results['mig_accel_decode_calls']}x"
             f"{'' if ad_ms is None else f'{ad_ms:.1f}ms'} "
             f"migrations={results['mig_migrations']}")
    if t_cluster is not None:
        per_eng = " ".join(
            f"{wid}={pe['tok_s']:.1f}tok/s(mig={pe['migrations']})"
            for wid, pe in results["cluster_per_engine"].items())
        emit("serve_cb/cluster", t_cluster * 1e6 / max(ctokens, 1),
             f"{results['cluster_tok_s']:.1f}tok/s n={args.cluster} "
             f"migrations={results['cluster_migrations']} {per_eng}")
    if t_cproc is not None:
        emit("serve_cb/cluster_proc", t_cproc * 1e6 / max(ptok, 1),
             f"{results['cluster_proc_tok_s']:.1f}tok/s "
             f"n={args.cluster_proc} "
             f"scaling={results['cluster_proc_scaling']:.2f}x "
             f"(1w={results['cluster_proc_1w_tok_s']:.1f}tok/s, "
             f"cores={results['cluster_proc_cores']}) "
             f"failures={results['cluster_proc_failures']}")
    if t_disagg is not None:
        emit("serve_cb/disagg", t_disagg * 1e6 / max(dtokens, 1),
             f"{results['disagg_tok_s']:.1f}tok/s "
             f"short_ttft_p99={results['disagg_ttft_p99_s'] * 1e3:.0f}ms"
             f"(mixed={results['disagg_baseline_ttft_p99_s'] * 1e3:.0f}"
             f"ms chunked={results['chunked_mixed_ttft_p99_s'] * 1e3:.0f}"
             f"ms) stall_max={results['decode_stall_ms']:.0f}ms "
             f"handoffs={results['disagg_handoffs']} "
             f"spans={results['disagg_spans']}")
    if t_spec is not None:
        emit("serve_cb/spec", t_spec * 1e6 / stok,
             f"{results['spec_on_tok_s']:.1f}tok/s "
             f"speedup={results['spec_speedup']:.2f}x "
             f"accept={results['spec_acceptance_rate']:.2f} "
             f"identical={int(results['spec_byte_identical'])} "
             f"split={results['spec_split_tok_s']:.1f}tok/s "
             f"draft_host={results['spec_draft_calls_host']} "
             f"verify_accel={results['spec_verify_calls_accel']}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)

    if args.check_floor:
        with open(args.check_floor) as f:
            floor = json.load(f)
        failed = []
        for key, bound in floor.items():
            # *_min keys are floors (got >= bound); *_max keys are
            # ceilings (got <= bound) — e.g. the TTFT latency bound
            ceiling = key.endswith("_max")
            name = key.removesuffix("_max" if ceiling else "_min")
            got = results.get(name)
            if got is None:
                # a bound with no matching result (typo'd key, renamed
                # metric, --no-paged) must fail loudly, not pass vacuously
                failed.append(f"{key}: no result named {name!r}")
            elif ceiling and got > bound:
                failed.append(f"{name}={got:.2f} > ceiling {bound}")
            elif not ceiling and got < bound:
                failed.append(f"{name}={got:.2f} < floor {bound}")
        if failed:
            print("FLOOR CHECK FAILED: " + "; ".join(failed),
                  file=sys.stderr)
            return 1
        print(f"floor check passed ({len(floor)} bounds)")
    return 0


if __name__ == "__main__":
    compile_cache.enable()
    sys.exit(main())
