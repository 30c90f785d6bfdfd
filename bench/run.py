#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``.  One process serves it and
holds its chips.  The run makes the weights from the seed on the
device, builds the served path, warms every shape the cell's traffic
can reach for both builds (set-up), measures for ``--seconds``, checks
what the window served against the plain reference, and prints one
JSON object as the last line of standard output.  ``--trace 1`` profiles
a span of the window and reports the cell's per-layer metrics instead
of its end-to-end ones.

It exits non-zero, printing no result, when JAX finds no TPU, fewer
chips than the cell asks for, or would run the Pallas kernels
interpreted.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import cells, check, observed, peaks, serve  # noqa: E402
from bench import trace as trace_lib  # noqa: E402
from bench import traffic as traffic_lib  # noqa: E402
from bench import weights  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
# the traced span: this far into the window, this long
TRACE_AT, TRACE_S = 0.5, 4.0


def enable_cache() -> str:
    """The persistent compile cache, at a fixed path inside the
    checkout, handed to the program through its environment variable."""
    import jax
    from repro.launch import compile_cache
    os.environ[compile_cache.ENV] = str(CACHE_DIR)
    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chips_or_none(chips: int):
    """The error that stops a run off a TPU, or None."""
    import jax
    from repro.kernels import ops
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"needs a TPU, JAX found {devs[0].platform}"
    if len(devs) < chips:
        return f"the cell needs {chips} chips, JAX sees {len(devs)}"
    if ops._interpret(None):
        return f"Pallas would run interpreted ({ops.INTERPRET_ENV})"
    return None


def engine_kw(cell: cells.Cell) -> dict:
    """The configuration's engine settings, with the pool sized to what
    the cell's traffic reaches: ``max_seq`` is the power of two that
    holds the longest prompt plus its output, within the configuration's
    own ``max_seq``."""
    from repro.serve.engine import prompt_bucket
    kw = dict(cell.config["engine"])
    need = prompt_bucket(traffic_lib.max_context(cell.traffic),
                         kw["block_size"])
    kw["max_seq"] = min(kw["max_seq"], need)
    return kw


def passes(gap: float, limit: float) -> bool:
    """The rule of ``correct``: a finite widest gap within the limit."""
    return math.isfinite(gap) and gap <= limit


def _decode_report(served) -> str:
    parts = []
    for i, rt in enumerate(served.runtimes):
        dec = [c for c in rt.call_log if c["fn"].endswith("_decode")]
        acc = sum(c["target"] == "accel" for c in dec)
        mig = rt.summary()["migrations"]
        parts.append(f"r{i}: accel {acc}/{len(dec)} decode calls, "
                     f"{mig} migrations")
    return "; ".join(parts)


def _log_window(served, w, diagnostics, gc_pauses) -> None:
    """Earlier lines of a run, not metrics: which build served the
    decode calls, the generator's lateness, peak bytes per device, and
    where the window stalled (slowest calls, longest spans with no
    token, garbage-collector pauses)."""
    import jax
    serve.log(_decode_report(served))
    if w.lateness:
        late = sorted(w.lateness)
        serve.log(f"generator lateness: median "
                  f"{late[len(late) // 2] * 1e3:.3f} ms, max "
                  f"{late[-1] * 1e3:.3f} ms over {len(late)} submissions")
    for e in served.engines:
        st = (e.device or jax.devices()[0]).memory_stats() or {}
        serve.log(f"{e.device}: peak_bytes_in_use "
                  f"{st.get('peak_bytes_in_use')}")
    serve.log(f"compiles inside the window: {w.compiles}")
    calls = sorted((c for log in w.calls for c in log), key=lambda c: -c["ms"])
    serve.log("slowest calls in the window (fn, build, ms): "
              f"{[(c['fn'], c['target'], round(c['ms'], 1)) for c in calls[:3]]}")
    gaps = serve.token_gaps(w.records, w.t0, w.t1)
    serve.log(f"longest spans with no token (s into the window, s): {gaps}")
    pauses = [(round(a - w.t0, 3), round(d, 4), g) for a, d, g in gc_pauses
              if w.t0 <= a <= w.t1]
    serve.log(f"gc pauses in the window: {len(pauses)}, longest "
              f"{sorted(pauses, key=lambda p: -p[1])[:3]}")
    depth = w.queue_depth
    if depth:
        serve.log(f"queued requests over the window (s, n): "
                  f"{[(round(a, 1), b) for a, b in depth[::4]]}")
    if diagnostics is not None:
        diagnostics.update(queue_depth=depth, compiles=w.compiles,
                           lateness_max_s=max(w.lateness, default=0.0),
                           decode=_decode_report(served), token_gaps=gaps,
                           gc_pauses=pauses[:20])


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, control: bool = False, rate=None,
             on_built=None, pk=None, diagnostics=None) -> dict:
    """Set up, measure, check; returns the result object.  ``control``
    also reads the control's gap; ``rate`` overrides a session mix's
    rate; ``on_built`` sees the served system before its warm-up (tests
    plant faults there); ``diagnostics`` collects what ``_log_window``
    logs."""
    import jax
    eng_kw = engine_kw(cell)
    if pk is None:
        pk = peaks.peaks(jax.devices()[0].device_kind)
    t = time.perf_counter()
    params = weights.make(cell.dims, seed)
    serve.log(f"weights {weights.param_count(cell.dims) / 1e9:.3f} B params "
              f"in {time.perf_counter() - t:.2f}s")
    cfg = serve.model_config(cell.config_name, cell.dims)
    t = time.perf_counter()
    served = serve.Served(cfg, params, eng_kw, cell.chips)
    try:
        serve.log(f"built {cell.chips} engine(s) in {time.perf_counter() - t:.2f}s")
        if on_built is not None:
            on_built(served)
        counter = serve.CompileCounter()
        gcw = serve.GcPauses()
        t = time.perf_counter()
        buckets = served.warm(traffic_lib.max_prompt(cell.traffic))
        serve.log(f"warmed prefill buckets {buckets} in "
                  f"{time.perf_counter() - t:.2f}s; not compiling: "
                  f"{sorted(set(served.uncompiled))}")
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        tracer = serve.TraceWindow(trace_dir, TRACE_AT * seconds,
                                   min(TRACE_S, 0.4 * seconds))
        runner = serve.RUNNERS[cell.traffic["kind"]]
        kw = {"rate": rate} if cell.traffic["kind"] == "sessions" else {}
        try:
            w = runner(served, cell.traffic, seed, seconds, tracer, counter,
                       **kw)
        finally:
            gcw.close()
        served.stamp_admissions(w.records)
        setup_s = w.t0 - t_start
        peak = served.peak_bytes()
        _log_window(served, w, diagnostics, gcw.pauses)
        tr = None
        if trace_dir is not None:
            path = trace_lib.find_xplane(trace_dir)
            if path is not None and tracer.t_end is not None:
                serve.log(trace_lib.describe(path))
                tr = trace_lib.reduce(path, (0.0, tracer.t_end - tracer.t_begin))
            shutil.rmtree(trace_dir, ignore_errors=True)
        max_slots = served.engines[0].slots.max_slots
        obs = observed.Observed(cell.dims, cell.chips, max_slots, pk, setup_s, w,
                                list(served.ctx_log), tr,
                                failed_ttft_s=serve.GRACE_S)
        names = [m["name"] for m in (cell.per_layer if trace else cell.end_to_end)]
        values = observed.read_all(names, obs)
        units = {m["name"]: m["unit"] for m in cell.per_layer + cell.end_to_end}
        metrics = {k: {"value": v, "unit": units[k]} for k, v in values.items()}
        if cell.traffic["kind"] == "offline":
            attempted = sum(1 for r in w.records
                            if r.complete and r.times[-1] < w.t1)
            failed = 0
        else:
            attempted = len(w.records)
            failed = sum(1 for r in w.records if not r.complete)
        for r in w.records:
            r.handle = None
    finally:
        served.close()
    del served
    # the check runs once the program's state is freed
    lim = cell.config["check"]
    t = time.perf_counter()
    recs = check.sample(w.records, seed, lim["sample_tokens"],
                        lim["sample_requests"])
    res = check.compare(params, cell.dims, recs,
                        control=control) if recs else {
        "max_logit_gap": float("nan"), "control_gap": float("nan"),
        "tokens": 0, "requests": 0, "replicas": 0}
    gap = res["max_logit_gap"]
    correct = bool(recs) and passes(gap, lim["max_logit_gap"])
    serve.log(f"reference check of {res['requests']} requests "
              f"({res['tokens']} served tokens, {res['replicas']} replicas) "
              f"in {time.perf_counter() - t:.2f}s")
    dev0 = jax.devices()[0]
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics,
           "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                      "count": cell.chips, "memory_peak_bytes": peak}}
    if tr is not None:
        out["device"]["busy_s"] = tr.busy_mean_s
        out["device"]["window_s"] = tr.window_s
        out["breakdown"] = tr.breakdown()
    checks = {}
    if control:
        # the control judged by the same rule: it has to come out false
        cgap = res["control_gap"]
        out["control_correct"] = bool(recs) and passes(
            cgap, lim["max_logit_gap"])
        checks["control_max_logit_gap"] = {"value": cgap,
                                           "limit": lim["max_logit_gap"]}
        serve.log(f"check control_max_logit_gap {cgap!r} limit "
                  f"{lim['max_logit_gap']!r}")
    checks["max_logit_gap"] = {"value": gap, "limit": lim["max_logit_gap"]}
    out["checks"] = checks
    serve.log(f"check max_logit_gap {gap!r} limit {lim['max_logit_gap']!r}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    err = chips_or_none(cell.chips)
    if err is not None:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    enable_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
