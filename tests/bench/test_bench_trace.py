"""The trace reduction, on hand-made intervals and on a trace recorded
on the CPU (which has no device plane)."""
import jax
import jax.numpy as jnp
import pytest

import _paths  # noqa: F401
from bench import trace


def test_union_and_gaps_by_hand():
    ivs = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6), (6.0, 7.0)]
    assert trace.union_seconds(ivs) == pytest.approx(4.0)
    assert trace.gaps(ivs, 0.0, 8.0) == [(2.0, 3.0), (4.0, 6.0), (7.0, 8.0)]
    assert trace.gaps(ivs, 0.5, 3.2) == [(2.0, 3.0)]
    assert trace.union_seconds([]) == 0.0
    assert trace.gaps([], 1.0, 2.0) == [(1.0, 2.0)]


def test_breakdown_averages_over_devices_and_keeps_ten():
    t = trace.Trace(devices=2, window_s=1.0, busy_s=[0.4, 0.6],
                    programs={"jit_decode_fn": [0.6, 10]},
                    ops={f"op{i}": float(i) for i in range(12)},
                    idle_gaps=[("python:step", 0.1)] * 12)
    b = t.breakdown()
    assert t.busy_mean_s == pytest.approx(0.5)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0] == ["op11", 5.5]
    assert t.program_seconds(r"^jit_decode_fn") == (0.3, 10)


def test_cpu_trace_has_no_device_plane(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    assert path is not None
    assert trace.reduce(path, (0.0, 1.0)) is None
    assert "/host:CPU" in trace.describe(path)
