#!/usr/bin/env python3
"""The serving program's own spans in a profiler trace.

The engine and the runtime mark their work with
``jax.profiler.TraceAnnotation`` spans (``engine.*`` in
``serve/engine.py``, ``runtime.*`` in ``core/runtime.py``): host events
on the same clock as the device planes, one line per thread.
``program_spans`` reads them from a trace; the helpers below are what
the span readers (``bench/metrics/loop_host_ms.py``,
``dispatch_host_ms.py``, ``decode_attn_hbm_roofline.py``) compute, from
``obs.trace.spans`` (``bench/trace.reduce`` fills it).

Run as a script on the chip, it serves a cell with a traced span, as
``bench/run.py --trace 1`` does, once per seed in one process:

    python3 bench/spans.py --workload <cell> --seeds 1,2,3 --seconds 51

and prints one JSON line per seed: the cell's per-layer metrics (the
span readers among them), the device's idle time in the span by the
innermost program span (and host event) over each idle gap, the clock
check of the traced span, and the decode call's host ms inside the
traced span against outside it (what tracing costs).
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import NamedTuple

if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import trace  # noqa: E402

PREFIXES = ("engine.", "runtime.")
WAIT = "runtime.wait"


class Span(NamedTuple):
    name: str
    start_s: float            # on the trace's clock
    end_s: float
    args: dict
    thread: str               # the host line ("<name>/<n>"): one per
                              # thread; Python's threads share a name


def program_spans(path: str, window: tuple[float, float]) -> list[Span]:
    """The host events named ``engine.*`` / ``runtime.*`` of the trace
    at ``path`` that overlap ``window`` (seconds on the trace's clock,
    whose zero is the start of the profiling session), by start time.
    Works on a trace with no device plane."""
    from jax.profiler import ProfileData
    lo, hi = window
    out = []
    lines = [ln for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for ln in p.lines]
    for n, line in enumerate(lines):
        for e in line.events:
            if not e.name.startswith(PREFIXES):
                continue
            s = e.start_ns * 1e-9
            end = s + e.duration_ns * 1e-9
            if end > lo and s < hi:
                out.append(Span(e.name, s, end, dict(e.stats),
                                f"{line.name}/{n}"))
    return sorted(out, key=lambda sp: sp.start_s)


def inside(inner: Span, outer: Span) -> bool:
    return (inner.thread == outer.thread and outer.start_s <= inner.start_s
            and inner.end_s <= outer.end_s)


def traced(trace, name: str) -> list[Span]:
    """Spans ``name`` that start in the traced span of a Trace (its
    window starts at 0); none without a trace."""
    if trace is None:
        return []
    return [s for s in trace.spans
            if s.name == name and 0.0 <= s.start_s < trace.window_s]


def host_seconds(spans: list[Span], outer: list[Span]) -> list[float]:
    """Each span of ``outer`` less the ``runtime.wait`` spans inside it:
    its time on the host, in seconds."""
    waits = [s for s in spans if s.name == WAIT]
    return [o.end_s - o.start_s - sum(w.end_s - w.start_s
                                      for w in waits if inside(w, o))
            for o in outer]


def host_ms(trace, name: str):
    """Mean host ms (``host_seconds``) of the spans ``name`` that start
    in the traced span; None where there are none."""
    outer = traced(trace, name)
    if not outer:
        return None
    return 1e3 * sum(host_seconds(trace.spans, outer)) / len(outer)


def served_on(trace, decodes: list[Span], target: str) -> list[Span]:
    """The decode spans whose runtime call ran on ``target``."""
    calls = [s for s in trace.spans if s.name == "runtime.call"
             and s.args.get("target") == target]
    return [d for d in decodes if any(inside(c, d) for c in calls)]


# ------------------------------------------------------------ the script
def _pieces(items, lo: float, hi: float) -> list:
    """[lo, hi) cut wherever one of ``items`` ((start, end, name))
    begins or ends: [(a, b, name of the shortest item over [a, b), or
    None)], in order."""
    cuts = sorted({lo, hi} | {t for s, e, _ in items for t in (s, e)
                              if lo < t < hi})
    order = sorted(items)
    active, out, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(order) and order[k][0] <= a:
            active.append(order[k])
            k += 1
        active = [x for x in active if x[1] > a]
        best = min(active, key=lambda x: x[1] - x[0]) if active else None
        out.append((a, b, best[2] if best else None))
    return out


def _apportion(pieces: list, gaps: list, none: str) -> dict:
    """Seconds of ``gaps`` (sorted) under each name of ``pieces``,
    longest first."""
    out: dict = {}
    k = 0
    for s, e in gaps:
        while k < len(pieces) and pieces[k][1] <= s:
            k += 1
        j = k
        while j < len(pieces) and pieces[j][0] < e:
            a, b, name = pieces[j]
            name = name or none
            out[name] = out.get(name, 0.0) + min(b, e) - max(a, s)
            j += 1
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def idle_by_span(path: str, window: tuple[float, float],
                 spans: list[Span]) -> dict:
    """The first device's idle time in ``window`` (its gaps between
    ops), each part of a gap put down to the innermost program span over
    it (``by_span``), and to the innermost host event of any kind
    (``by_event``, the longest twelve)."""
    from jax.profiler import ProfileData
    lo, hi = window
    planes = list(ProfileData.from_file(path).planes)
    dev = next(p for p in planes if trace.DEVICE_PLANE.match(p.name))
    lines = {ln.name: ln for ln in dev.lines}
    busy = next(lines[n] for n in trace.OP_LINES + trace.MODULE_LINES
                if n in lines)
    gaps = trace.gaps([(e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9)
                       for e in busy.events], lo, hi)
    host = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
             f"{ln.name}:{e.name}")
            for p in planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]
    by_span = _apportion(_pieces([(sp.start_s, sp.end_s, sp.name)
                                  for sp in spans], lo, hi),
                         gaps, "no program span")
    by_event = _apportion(_pieces(host, lo, hi), gaps, "no host event")
    return {"idle_s": sum(e - s for s, e in gaps), "gaps": len(gaps),
            "by_span": by_span,
            "by_event": [[k, v] for k, v in list(by_event.items())[:12]]}


def _capture() -> dict:
    """Keep what ``run.run_cell`` reduces and throws away: the traced
    run's Trace, the idle time by span, and the measured window."""
    from bench import serve
    got: dict = {}
    reduce, runners = trace.reduce, dict(serve.RUNNERS)

    def reduce_keeping(path, window, **kw):
        tr = reduce(path, window, **kw)
        if tr is not None:
            got["idle"] = idle_by_span(path, window, tr.spans)
            got["trace"] = tr
        return tr

    def keeping(run):
        def runner(*a, **kw):
            got["window"] = run(*a, **kw)
            return got["window"]
        return runner

    trace.reduce = reduce_keeping
    serve.RUNNERS.update({k: keeping(v) for k, v in runners.items()})
    return got


def report(got: dict) -> dict:
    """What one traced run shows beyond its metrics."""
    tr, w = got["trace"], got["window"]
    lo, hi = w.trace.t_begin, w.trace.t_end
    its = traced(tr, "engine.iteration")
    decodes = traced(tr, "engine.decode")
    host_rows = sum(1 for r in w.records for i, t in enumerate(r.times)
                    if i and lo <= t < hi)
    calls = [c for log in w.calls for c in log
             if c["fn"].endswith("_decode") and w.t0 <= c["t"] < w.t1]
    ins = [c["ms"] for c in calls if lo <= c["t"] < hi]
    out = [c["ms"] for c in calls if not lo <= c["t"] < hi]
    idle = got["idle"]
    named = idle["idle_s"] - idle["by_span"].get("no program span", 0.0)
    return {
        "window_s": tr.window_s,
        "iterations": len(its),
        "loop_host_share": sum(host_seconds(tr.spans, its)) / tr.window_s,
        "idle_named_frac": named / idle["idle_s"] if idle["idle_s"] else None,
        "idle": idle,
        "clock": {"host_decode_tokens": host_rows,
                  "spans_from_to": [min(s.start_s for s in tr.spans),
                                    max(s.end_s for s in tr.spans)],
                  "span_decode_rows": sum(s.args.get("rows", 0)
                                          for s in decodes),
                  "decode_steps": len(decodes),
                  "program_decode_runs": tr.program_seconds(
                      r"^jit_decode_fn")[1]},
        "decode_call_ms": {"traced": sum(ins) / max(len(ins), 1),
                           "untraced": sum(out) / max(len(out), 1),
                           "calls": [len(ins), len(out)]},
    }


def main(argv=None) -> int:
    import argparse
    import time

    from bench import cells, run
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    err = run.chips_or_none(cell.chips)
    if err is not None:
        print(f"spans: {err}", file=sys.stderr)
        return 2
    run.enable_cache()
    got = _capture()
    for seed in (int(s) for s in args.seeds.split(",")):
        got.clear()
        out = run.run_cell(cell, seed, args.seconds, True, time.perf_counter())
        line = {"seed": seed, "correct": out["correct"],
                "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        if "trace" in got:
            line.update(report(got))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
