"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to the numbers
the per-layer metrics read: device busy time, device time per program,
the top device operations, the longest idle gaps, each named by what
the host was doing meanwhile, and the program's own spans.

Device planes are ``/device:TPU:<n>``.  Busy time is the union of the
intervals of the plane's op line (``XLA Ops``; the module line when a
trace has no op line); a program's device time is the sum of its
events on the module line (``XLA Modules``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# control-flow ops span the ops of their bodies; the op table leaves them
# out so that time is not counted twice
NESTING_OPS = re.compile(r"^%?(while|conditional|call)[.\d]*$")
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
TOP = 10


def union_seconds(intervals: Iterable[tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals (seconds)."""
    total, end = 0.0, None
    start = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                total += end - start
            start, end = s, e
        else:
            end = max(end, e)
    if end is not None:
        total += end - start
    return total


def gaps(intervals: Iterable[tuple[float, float]], lo: float,
         hi: float) -> list[tuple[float, float]]:
    """Idle [start, end) stretches of [lo, hi) not covered by any interval."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Trace:
    devices: int
    window_s: float
    busy_s: list                     # per device
    programs: dict                   # program name -> [seconds, count], summed
    ops: dict                        # op name -> seconds, summed
    idle_gaps: list                  # [(host activity, seconds)], longest first
    # the program's spans over the window (bench/spans.program_spans)
    spans: list = dataclasses.field(default_factory=list)

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s) / max(len(self.busy_s), 1)

    def program_seconds(self, pattern: str) -> tuple[float, int]:
        """Summed device seconds and executions of the programs whose
        name matches ``pattern``, averaged over the devices."""
        rx = re.compile(pattern)
        s = sum(v[0] for k, v in self.programs.items() if rx.search(k))
        n = sum(v[1] for k, v in self.programs.items() if rx.search(k))
        return s / max(self.devices, 1), n

    def breakdown(self) -> dict:
        n = max(self.devices, 1)
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v / n] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:TOP]]}


def op_name(event_name: str) -> str:
    """``%paged_gqa_decode.9 = bf16[...] custom-call(...)`` ->
    ``paged_gqa_decode``: the HLO name without its instance number."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def _events(line, t0_ns: float):
    for e in line.events:
        s = (e.start_ns - t0_ns) * 1e-9
        yield e.name, s, s + e.duration_ns * 1e-9


def reduce(path: str, window: tuple[float, float],
           device_plane=DEVICE_PLANE) -> Optional[Trace]:
    """Reduce one trace; ``window`` is the traced span ``(lo, hi)`` in
    seconds on the trace's own clock, whose zero is the start of the
    profiling session.  Returns None when the trace holds no device
    plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    dev = [p for p in planes if device_plane.match(p.name)]
    if not dev:
        return None
    lo, hi = window
    busy, programs, ops, first_gaps = [], {}, {}, None
    for plane in dev:
        lines = {ln.name: ln for ln in plane.lines}
        op_line = next((lines[n] for n in OP_LINES if n in lines), None)
        mod_line = next((lines[n] for n in MODULE_LINES if n in lines), None)
        ivs = []
        for name, s, e in _events(op_line or mod_line, 0) if (
                op_line or mod_line) else ():
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            ivs.append((s, e))
            op = op_name(name)
            if op_line is not None and not NESTING_OPS.match(op):
                ops[op] = ops.get(op, 0.0) + (e - s)
        if mod_line is not None:
            for name, s, e in _events(mod_line, 0):
                s, e = max(s, lo), min(e, hi)
                if e <= s:
                    continue
                key = re.sub(r"\(\d+\)$", "", name)
                acc = programs.setdefault(key, [0.0, 0])
                acc[0] += e - s
                acc[1] += 1
        busy.append(union_seconds(ivs))
        if first_gaps is None:
            first_gaps = gaps(ivs, lo, hi)
    host = _host_events(planes, lo, hi)
    named = sorted(((_doing(host, s, e), e - s) for s, e in first_gaps or ()),
                   key=lambda kv: -kv[1])
    from bench import spans         # which imports this module
    return Trace(len(dev), hi - lo, busy, programs, ops, named,
                 spans.program_spans(path, window))


def _host_events(planes, lo, hi) -> list[tuple[float, float, str]]:
    out = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for name, s, e in _events(line, 0):
                if e > lo and s < hi:
                    out.append((s, e, f"{line.name}:{name}"))
    return out


def _doing(host, s: float, e: float) -> str:
    """The innermost host event that covers the middle of [s, e)."""
    mid = (s + e) / 2
    best = None
    for hs, he, name in host:
        if hs <= mid < he and (best is None or he - hs < best[1] - best[0]):
            best = (hs, he, name)
    return best[2] if best else "no host event"



def describe(path: str, top: int = 8) -> str:
    """Planes, lines with their event counts and first and last start
    times, and the longest programs: what a reader of a new trace checks
    names and the clock against."""
    from jax.profiler import ProfileData
    out = []
    for p in ProfileData.from_file(path).planes:
        lines = []
        for ln in p.lines:
            starts = [e.start_ns for e in ln.events]
            if starts:
                lines.append(f"{ln.name}({len(starts)}, "
                             f"{min(starts) * 1e-9:.3f}-"
                             f"{max(starts) * 1e-9:.3f}s)")
        if lines:
            out.append(f"{p.name}: {', '.join(lines[:12])}")
        if DEVICE_PLANE.match(p.name):
            mods = {}
            for ln in p.lines:
                if ln.name in MODULE_LINES:
                    for e in ln.events:
                        k = re.sub(r"\(\d+\)$", "", e.name)
                        mods[k] = mods.get(k, 0.0) + e.duration_ns * 1e-9
            best = sorted(mods.items(), key=lambda kv: -kv[1])[:top]
            out.append(f"  programs: {best}")
    return "\n".join(out)
