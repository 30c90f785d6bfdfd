"""The program's spans: recorded by the tiny engine under the profiler on
the CPU and read back by ``bench/spans.py``, and the span readers on
hand-made traces, by hand and where they find nothing."""
import json
import types

import jax
import numpy as np
import pytest

import _paths  # noqa: F401
from _tiny import OFFLINE, tiny
from bench import cells, flops, observed, run, serve, spans, trace, weights

TABLE = {"engine.iteration", "engine.admit", "engine.prefill_chunk",
         "engine.decode", "engine.blocks", "engine.upload",
         "engine.readback", "engine.emit", "engine.sleep",
         "runtime.call", "runtime.decide", "runtime.wait"}
DIMS = cells.model_dims(json.loads(
    (cells.ROOT / "bench/configs/yi-6b.json").read_text()))
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def served_trace(tmp_path_factory):
    """A few requests through the tiny offline cell's engine with the
    profiler on: the trace's program spans, and what the engine held at
    each decode step (rows, summed pool positions)."""
    from repro.serve import GenerationRequest
    cell = tiny(*OFFLINE)
    params = weights.make(cell.dims, 3)
    cfg = serve.model_config(cell.config_name, cell.dims)
    served = serve.Served(cfg, params, run.engine_kw(cell), 1)
    eng = served.engines[0]
    steps = []
    ensure = eng._ensure_decode_blocks

    def noting():
        ensure()
        active = eng.slots.active_slots()
        steps.append((len(active), sum(s.pos for s in active)))

    eng._ensure_decode_blocks = noting
    rng = np.random.RandomState(0)

    def reqs(arrivals):
        return [GenerationRequest(rng.randint(1, 200, size=n).astype(np.int32),
                                  max_new_tokens=k, arrival_s=a)
                for n, k, a in arrivals]

    # the last arrives after the others finished: the loop sleeps
    plan = [(24, 4, 0.0), (40, 5, 0.0), (70, 3, 0.0), (30, 3, 0.5)]
    try:
        eng.run(reqs(plan))             # compiles every shape untraced
        steps.clear()
        log_dir = str(tmp_path_factory.mktemp("trace"))
        jax.profiler.start_trace(log_dir)
        eng.run(reqs(plan))
        jax.profiler.stop_trace()
    finally:
        served.close()
    path = trace.find_xplane(log_dir)
    return spans.program_spans(path, (0.0, 1e9)), steps


def test_every_span_of_the_table_is_recorded(served_trace):
    sp, _ = served_trace
    assert {s.name for s in sp} == TABLE
    assert all(s.name.startswith(spans.PREFIXES) for s in sp)


def test_spans_nest_wait_call_step_iteration(served_trace):
    sp, _ = served_trace
    by = lambda name: [s for s in sp if s.name == name]
    for wait in by("runtime.wait"):
        call = [c for c in by("runtime.call") if spans.inside(wait, c)]
        assert len(call) == 1
        step = [s for s in by("engine.decode") + by("engine.prefill_chunk")
                if spans.inside(call[0], s)]
        assert len(step) == 1
        assert any(spans.inside(step[0], it) for it in by("engine.iteration"))
    for call in by("runtime.call"):
        assert call.args["target"] in ("host", "accel")
        assert call.args["fn"].endswith(("_decode", "_prefill_ctx"))
    for dec in by("engine.decode"):
        for part in ("engine.blocks", "engine.upload", "engine.readback",
                     "engine.emit"):
            assert sum(spans.inside(p, dec) for p in by(part)) == 1
    for chunk in by("engine.prefill_chunk"):
        assert chunk.args["width"] <= chunk.args["bucket"]
    assert sorted(a.args["req"] for a in by("engine.admit")) == sorted(
        {a.args["req"] for a in by("engine.admit")})


def test_decode_span_counts_rows_and_positions(served_trace):
    sp, steps = served_trace
    dec = [s for s in sp if s.name == "engine.decode"]
    assert [(s.args["rows"], s.args["ctx"]) for s in dec] == steps
    assert [s.args["step"] for s in dec] == sorted(s.args["step"]
                                                   for s in dec)
    assert sum(r for r, _ in steps) == (4 - 1) + (5 - 1) + (3 - 1) + (3 - 1)


def test_program_spans_keep_the_window(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("engine.iteration", queued=2,
                                          active=1):
            pass
        with jax.profiler.TraceAnnotation("other.span"):
            pass
    path = trace.find_xplane(str(tmp_path))
    got = spans.program_spans(path, (0.0, 1e9))
    assert [(s.name, s.args) for s in got] == [
        ("engine.iteration", {"queued": 2, "active": 1})]
    assert spans.program_spans(path, (1e8, 1e9)) == []


# ---------------------------------------------------------- the readers
def _span(name, start, end, thread="engine-loop", **args):
    return spans.Span(name, start, end, args, thread)


def _trace(sp, ops=None, window_s=1.0):
    t = trace.Trace(devices=1, window_s=window_s, busy_s=[0.8],
                    programs={}, ops=dict(ops or {}), idle_gaps=[])
    t.spans = sp
    return t


def _obs(tr):
    w = types.SimpleNamespace(t0=0.0, t1=10.0, records=[], calls=[[]],
                              stats=[], trace=None)
    return observed.Observed(DIMS, 1, 32, PEAKS, 1.0, w, [], tr)


def _step(t, ctx, target="accel", thread="engine-loop"):
    """One loop pass at ``t``: 2 ms of host, a 20 ms decode call of
    which 18 ms waits on the chip, 1 ms of host after."""
    return [_span("engine.iteration", t, t + 0.023, thread, queued=0,
                  active=2),
            _span("engine.decode", t + 0.002, t + 0.023, thread, step=0,
                  rows=2, ctx=ctx),
            _span("runtime.call", t + 0.002, t + 0.022, thread,
                  fn="w0_decode", target=target),
            _span("runtime.wait", t + 0.003, t + 0.021, thread)]


def test_span_readers_by_hand():
    sp = (_step(0.1, 300) + _step(0.2, 302) + _step(0.3, 304, "host")
          + _step(0.5, 1000, thread="engine-1")
          + _step(1.5, 9999))                   # starts after the span
    tr = _trace(sp, ops={"paged_gqa_decode": 0.004})
    v = observed.read_all(["loop_host_ms", "dispatch_host_ms",
                           "decode_attn_hbm_roofline"], _obs(tr))
    # 23 ms a pass less 18 ms of wait; 20 ms a call less its 18 ms
    assert v["loop_host_ms"] == pytest.approx(5.0)
    assert v["dispatch_host_ms"] == pytest.approx(2.0)
    # the ACCEL steps only: 300 + 302 + 1000 positions in 4 ms
    least = flops.kv_bytes_per_token(DIMS) * 1602 / PEAKS["hbm_bytes_per_s"]
    assert v["decode_attn_hbm_roofline"] == pytest.approx(
        100 * least / 0.004)


def test_a_wait_on_another_thread_is_not_inside():
    sp = _step(0.1, 300)
    sp[3] = sp[3]._replace(thread="engine-1")
    tr = _trace(sp)
    assert spans.host_ms(tr, "engine.iteration") == pytest.approx(23.0)
    assert spans.host_ms(tr, "runtime.call") == pytest.approx(20.0)


@pytest.mark.parametrize("case", ["no_trace", "no_spans", "empty_spans",
                                  "no_kernel_op", "host_served_all"])
def test_span_readers_find_nothing(case):
    names = ["loop_host_ms", "dispatch_host_ms", "decode_attn_hbm_roofline"]
    sp = _step(0.1, 300)
    if case == "no_trace":
        tr = None
    elif case == "no_spans":            # a Trace made with none
        tr = trace.Trace(devices=1, window_s=1.0, busy_s=[0.8], programs={},
                         ops={"paged_gqa_decode": 0.004}, idle_gaps=[])
    elif case == "empty_spans":
        tr = _trace([], ops={"paged_gqa_decode": 0.004})
    elif case == "no_kernel_op":        # HOST served the decode
        tr = _trace(sp, ops={"fusion": 0.004})
        names = names[2:]
    else:
        tr = _trace(_step(0.1, 300, "host"), ops={"paged_gqa_decode": 0.004})
        names = names[2:]
    assert observed.read_all(names, _obs(tr)) == {}


def test_idle_gaps_go_to_the_innermost_span_over_each_part():
    items = [(0.0, 10.0, "engine.iteration"), (2.0, 4.0, "engine.upload"),
             (6.0, 9.0, "runtime.wait")]
    pieces = spans._pieces(items, 0.0, 12.0)
    assert pieces == [(0.0, 2.0, "engine.iteration"),
                      (2.0, 4.0, "engine.upload"),
                      (4.0, 6.0, "engine.iteration"),
                      (6.0, 9.0, "runtime.wait"),
                      (9.0, 10.0, "engine.iteration"), (10.0, 12.0, None)]
    got = spans._apportion(pieces, [(1.0, 3.0), (5.0, 7.0), (9.5, 12.0)],
                           "no program span")
    assert got == pytest.approx({"engine.iteration": 2.5,
                                 "no program span": 2.0,
                                 "engine.upload": 1.0, "runtime.wait": 1.0})
    assert list(got)[:2] == ["engine.iteration", "no program span"]
