"""Benchmark orchestrator: one module per paper table/figure plus the
kernel and roofline benches.  Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run [--only fig6,table2]
"""
import argparse
import sys
import traceback

MODULES = [
    "benchmarks.bench_table1",
    "benchmarks.bench_table2",
    "benchmarks.bench_table4",
    "benchmarks.bench_fig3",
    "benchmarks.bench_fig45",
    "benchmarks.bench_fig6",
    "benchmarks.bench_fig78",
    "benchmarks.bench_fig9",
    "benchmarks.bench_fig10",
    "benchmarks.bench_kernels",
    "benchmarks.bench_roofline",
    "benchmarks.bench_serve_cb",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()
    wanted = [w for w in args.only.split(",") if w]
    print("name,us_per_call,derived")
    failures = []
    for modname in MODULES:
        if wanted and not any(w in modname for w in wanted):
            continue
        try:
            mod = __import__(modname, fromlist=["main"])
            mod.main()
        except Exception as e:  # noqa: BLE001
            failures.append((modname, repr(e)))
            traceback.print_exc(limit=3, file=sys.stderr)
            print(f"{modname},0.0,ERROR({e!r})")
    if failures:
        raise SystemExit(f"{len(failures)} benchmark modules failed")


if __name__ == "__main__":
    main()
