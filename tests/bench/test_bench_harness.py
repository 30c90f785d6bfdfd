"""The harness end to end on the CPU at toy sizes (the look for a chip
is skipped), and its refusal to report without one."""
import json
import os
import subprocess
import sys
import time

import pytest

import _paths  # noqa: F401
from _tiny import OFFLINE, OPEN, PEAKS, tiny
from bench import run

ROOT = _paths.ROOT
CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]


def _keys_ok(out, cell):
    assert list(out)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    for name, m in out["metrics"].items():
        assert m["value"] > 0 and m["unit"]
    json.dumps(out, allow_nan=False)


def test_offline_cell_end_to_end():
    cell = tiny(*OFFLINE)
    out = run.run_cell(cell, 2**33 + 5, 2.0, False, time.perf_counter(),
                       pk=PEAKS)
    _keys_ok(out, cell)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert out["attempted"] > 0


def test_open_loop_cell_end_to_end_traced():
    cell = tiny(*OPEN)
    out = run.run_cell(cell, 7, 2.0, True, time.perf_counter(), pk=PEAKS)
    _keys_ok(out, cell)
    assert out["correct"] is True
    # a CPU trace has no device plane: the trace-read metrics stay out
    assert set(out["metrics"]) == {m["name"] for m in cell.per_layer
                                   if m["source"] != "device_trace"}
    # a wait inside a 2 s window, not the time since the engine started
    assert out["metrics"]["queue_wait_p95_ms"]["value"] < 2000


def test_four_replicas_on_virtual_devices():
    """The sessions cell's router over four replicas, one per device."""
    code = (
        "import sys, json, time; sys.path[:0] = ['tests/bench']\n"
        "from _tiny import OPEN, PEAKS, tiny\n"
        "from bench import run\n"
        "out = run.run_cell(tiny(*OPEN, chips=4), 11, 2.0, False, "
        "time.perf_counter(), pk=PEAKS)\n"
        "print(json.dumps(out))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4 and out["correct"] is True
    assert "4 engine(s)" in p.stderr


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CELL,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_in_a_directory_without_the_program(tmp_path):
    for rel in ["BENCHMARK.json", "bench"]:
        src = ROOT / rel
        if src.is_dir():
            import shutil
            shutil.copytree(src, tmp_path / rel,
                            ignore=shutil.ignore_patterns("__pycache__"))
        else:
            (tmp_path / rel).write_text(src.read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CELL,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("seed", [0, 2**31 + 3, 2**40 + 9])
def test_large_seeds_make_distinct_weights(seed):
    from bench import weights
    words = weights.seed_words(seed)
    assert words != weights.seed_words(seed + 1)
    assert all(0 <= w < 2**32 for w in words)
