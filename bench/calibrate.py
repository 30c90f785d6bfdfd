#!/usr/bin/env python3
"""Readings for the limits of ``correct`` and for a cell's offered rate.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20 [--rates 2,4,8] [--bench FILE]

Runs the cell once per (seed, rate) in this one process — the chip
belongs to one process, and later builds load their programs from the
compile cache — with the control on: every run prints, besides the
program's ``max_logit_gap``, the gap of the control (the reference at
float8 operands, ``bench/reference.py``) over the same prompts and
served tokens.  ``--rates`` overrides a session mix's rate, for the
sweep that finds the highest rate the system sustains: rates run in the
order given, and the sweep stops after the first whose queue of
unadmitted requests ends the window above ``GROWN`` per chip.
``--bench`` reads the cell from another file in the form of
``BENCHMARK.json``, for a candidate cell not yet admitted.  One JSON
line per run on standard output, with the queue's trajectory.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent / "src"),
                str(Path(__file__).resolve().parent.parent)]

from bench import cells, run  # noqa: E402

GROWN = 8


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--bench", default="BENCHMARK.json")
    args = ap.parse_args(argv)
    cell = cells.load(args.workload, bench_file=args.bench)
    err = run.chips_or_none(cell.chips)
    if err is not None:
        print(f"calibrate: {err}", file=sys.stderr)
        return 2
    run.enable_cache()
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    for seed in (int(s) for s in args.seeds.split(",")):
        for rate in rates:
            t = time.perf_counter()
            diag: dict = {}
            try:
                out = run.run_cell(cell, seed, args.seconds, bool(args.trace),
                                   t, control=True, rate=rate,
                                   diagnostics=diag)
            except Exception as e:  # noqa: BLE001 — report and go on
                print(json.dumps({"seed": seed, "rate": rate,
                                  "error": f"{type(e).__name__}: {e}"[:2000]}),
                      flush=True)
                continue
            print(json.dumps({"seed": seed, "rate": rate, **out,
                              "diagnostics": diag}), flush=True)
            depth = diag.get("queue_depth")
            if rate is not None and depth and depth[-1][1] > GROWN * cell.chips:
                break
    return 0


if __name__ == "__main__":
    sys.exit(main())
