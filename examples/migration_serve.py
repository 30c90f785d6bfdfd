"""Continuous-batching serving with run-time execution migration (the
Fig-6 scenario on real JAX functions), on the v2 serve API.

A reduced model serves a ragged Poisson arrival stream through the
``ContinuousBatchingEngine``; every prefill/decode step dispatches
through the Xar-Trek runtime.  The engine registers genuinely different
builds of its step functions — HOST on the XLA reference math, ACCEL on
the Pallas kernels (flash prefill, paged/flash decode) — so a migration
is a real kernel swap.  The scheduler watches the synthetic host load,
pre-configures the ACCEL variant asynchronously at startup, and
migrates decode steps when the load crosses the threshold.

Requests are v2 ``GenerationRequest``s: half the stream samples with
per-request seeds (temperature 0.8, top-k 40) through the IN-GRAPH
sampler — the decode step keeps one static compile signature for any
request mix, and a seeded request reproduces the same tokens no matter
which target serves each step.  Results come back as ``RequestOutput``
(finish reason + TTFT/TPOT metrics), and one request is consumed as a
live token stream via its ``RequestHandle``.

    PYTHONPATH=src python examples/migration_serve.py [--backend auto]

Placement is a pluggable ``SchedulingPolicy`` (core/policy):
``--backend`` picks one — ``host``/``accel`` are the ``PinHost`` /
``PinAccel`` static policies, ``auto`` (default) is ``XarTrekHeuristic``
(Algorithm 2) fed by REAL engine telemetry: the engine publishes a
``LoadSignals`` snapshot (queue depth, free KV, per-target decode ms)
every loop iteration, and the synthetic co-tenant counter is merged in
as one more signal source.
"""
import argparse
import threading
import time

import numpy as np

from repro.configs import ARCHS, reduced
from repro.core.function import FunctionRegistry
from repro.core.policy import PinAccel, PinHost, XarTrekHeuristic
from repro.core.runtime import XarTrekRuntime
from repro.core.targets import TargetKind
from repro.launch import compile_cache
from repro.serve import (ContinuousBatchingEngine, GenerationRequest,
                         SamplingParams)
from repro.serve.scheduler import poisson_arrivals


def make_stream(vocab: int, n: int, rate_per_s: float, seed: int = 0):
    """Mixed stream: even requests greedy, odd requests sampled with a
    per-request seed — all through ONE decode signature."""
    rng = np.random.RandomState(seed)
    return [GenerationRequest(
        rng.randint(0, vocab, size=int(rng.randint(6, 28))),
        max_new_tokens=int(rng.randint(4, 16)), arrival_s=t,
        sampling=(SamplingParams(temperature=0.8, top_k=40, seed=seed * 100 + i)
                  if i % 2 else SamplingParams()))
        for i, t in enumerate(poisson_arrivals(n, rate_per_s, seed))]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--backend", choices=("host", "accel", "auto"),
                    default="auto",
                    help="pin every step to one build, or let the "
                         "scheduler migrate (auto)")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = reduced(ARCHS["smollm-135m"])
    policy = {"host": PinHost(), "accel": PinAccel(),
              "auto": XarTrekHeuristic()}[args.backend]
    rt = XarTrekRuntime(registry=FunctionRegistry(),
                        min_reconfig_seconds=1.0 if args.backend == "auto"
                        else 0.0)
    # auto keeps the paper's asynchronous FPGA pre-configuration (the
    # latency-hiding demo below); only accel-pinned runs compile the
    # ACCEL build eagerly (host-pinned never calls it — don't stall on it)
    engine = ContinuousBatchingEngine(cfg, max_slots=4, max_seq=96,
                                      runtime=rt, seed=0, policy=policy,
                                      eager_accel=args.backend == "accel")
    # threshold row for the decode step: ACCEL profitable once the real
    # load (queued requests + synthetic co-tenants) crosses ~6
    row = rt.table.row("cb_decode")
    row.fpga_thr, row.arm_thr = 6.0, 1e9

    # --- streaming demo: consume one request token-by-token while the
    # engine loop drains in another thread
    handle = engine.submit(np.arange(1, 11, dtype=np.int32) % cfg.vocab_size,
                           max_new_tokens=8,
                           sampling=SamplingParams(temperature=0.8,
                                                   top_k=40, seed=1234))
    t = threading.Thread(target=engine.run)
    t.start()
    streamed = [tok for tok in handle]          # blocks per token
    t.join()
    out = handle.result()
    print(f"streamed  : {streamed} finish={out.finish_reason} "
          f"ttft={out.ttft_s * 1e3:.0f}ms tpot={out.tpot_s * 1e3:.1f}ms")

    phases = [("low load", 0), ("high load", 6)]
    for pi, (phase, synthetic_load) in enumerate(phases):
        if pi == 1 and args.backend == "auto":
            # lull between phases: the asynchronous "reconfiguration"
            # (ACCEL compile) completes while traffic is elsewhere —
            # the paper's latency-hiding behaviour
            deadline = time.time() + 10.0
            while (not rt.bank.is_resident("cb_decode")
                   and time.time() < deadline):
                time.sleep(0.05)
        for _ in range(synthetic_load):      # synthetic co-tenants
            rt.monitor.job_started(TargetKind.HOST)
        mark = len(rt.call_log)
        reqs = make_stream(cfg.vocab_size, n=12, rate_per_s=30.0, seed=pi)
        t0 = time.perf_counter()
        outs = engine.run(reqs)
        dt = time.perf_counter() - t0
        for _ in range(synthetic_load):
            rt.monitor.job_finished(TargetKind.HOST)
        tokens = sum(o.n_tokens for o in outs.values())
        ttft = sorted(o.ttft_s for o in outs.values())
        targets = [rec["target"] for rec in rt.call_log[mark:]]
        finish = {}
        for o in outs.values():
            finish[o.finish_reason] = finish.get(o.finish_reason, 0) + 1
        print(f"{phase:10s}: {tokens / dt:7.1f} tok/s  "
              f"ttft_p50={ttft[len(ttft) // 2] * 1e3:.0f}ms "
              f"finish={finish}  "
              f"targets={dict((t, targets.count(t)) for t in set(targets))}")
    print("summary:", rt.summary())


if __name__ == "__main__":
    main()
