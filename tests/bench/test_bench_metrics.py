"""Every metric reader on a hand-made observation: values by hand, and
nothing (rather than 0) where a reader finds nothing to read."""
import json
import types

import numpy as np
import pytest

import _paths  # noqa: F401
from bench import cells, flops, observed, trace

BENCH = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
DIMS = cells.model_dims(json.loads(
    (cells.ROOT / "bench/configs/yi-6b.json").read_text()))
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _rec(t_due, times, prompt_len=100, t_admit=None):
    return types.SimpleNamespace(t_due=t_due, times=list(times),
                                 prompt=np.zeros(prompt_len, np.int32),
                                 t_admit=t_admit, aborted=False)


def _obs(records, tr=None, span=None, ctx_log=(), stats=None, calls=()):
    tw = None
    if span is not None:
        tw = types.SimpleNamespace(t_begin=span[0], t_end=span[1])
    w = types.SimpleNamespace(t0=10.0, t1=20.0, records=records,
                              calls=[list(calls)], stats=stats or [],
                              trace=tw)
    return observed.Observed(DIMS, 1, 32, PEAKS, 42.0, w, list(ctx_log), tr,
                             failed_ttft_s=60.0)


def test_every_metric_has_a_reader():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(observed.reader(m["name"]))


def test_latency_and_rate_readers():
    recs = [_rec(10.0 + i, [10.0 + i + 0.1 + 0.01 * k for k in range(5)])
            for i in range(19)] + [_rec(19.5, [])]      # one never served
    obs = _obs(recs)
    v = observed.read_all(["ttft_p95_ms", "itl_p95_ms", "tokens_per_s",
                           "setup_s"], obs)
    # 19 requests at 100 ms and one charged the 60 s grace
    assert v["ttft_p95_ms"] == pytest.approx(
        np.percentile([100.0] * 19 + [60_000.0], 95))
    assert v["itl_p95_ms"] == pytest.approx(10.0)
    in_window = sum(1 for r in recs for t in r.times if 10.0 <= t < 20.0)
    assert v["tokens_per_s"] == pytest.approx(in_window / 10.0)
    assert v["setup_s"] == 42.0


def test_counter_readers():
    stats = [{"decode_steps": 10, "decode_row_util": 5.0,
              "prefix": {"prefix_hit_tokens": 30, "prefill_tokens": 70}}]
    calls = [{"fn": "w0_decode", "ms": 2.0, "target": "host"},
             {"fn": "w0_decode", "ms": 4.0, "target": "accel"},
             {"fn": "w0_prefill_ctx", "ms": 9.0, "target": "host"}]
    recs = [_rec(10.0, [10.5], t_admit=10.0 + 0.001 * i) for i in range(21)]
    recs.append(_rec(10.0, []))          # never admitted: nothing to read
    v = observed.read_all(["decode_rows_mean", "prefix_hit_frac",
                           "decode_call_ms.open", "prefill_call_ms",
                           "queue_wait_p95_ms"],
                          _obs(recs, stats=stats, calls=calls))
    assert v == pytest.approx({"decode_rows_mean": 16.0,
                               "prefix_hit_frac": 0.3,
                               "decode_call_ms.open": 3.0,
                               "prefill_call_ms": 9.0,
                               "queue_wait_p95_ms": 19.0})


def test_trace_readers_by_hand():
    # one request decoding 10 tokens inside the span, 10 steps of 20 ms
    rec = _rec(10.0, [11.0] + [12.0 + 0.02 * k for k in range(1, 11)],
               prompt_len=1000)
    tr = trace.Trace(devices=1, window_s=1.0, busy_s=[0.8],
                     programs={"jit_decode_fn": [0.2, 10]}, ops={},
                     idle_gaps=[])
    ctx_log = [(12.1, 0, 512, 256)]
    obs = _obs([rec], tr=tr, span=(12.0, 13.0), ctx_log=ctx_log)
    v = observed.read_all(["device_idle_frac.offline", "decode_hbm_roofline",
                           "step_mfu"], obs)
    assert v["device_idle_frac.offline"] == pytest.approx(0.2)
    kv = sum(1000 + i - 1 for i in range(1, 11))
    byts = 10 * flops.weight_bytes(DIMS) + flops.kv_bytes_per_token(DIMS) * (
        kv + 10)
    assert v["decode_hbm_roofline"] == pytest.approx(
        100 * byts / 819e9 / 0.2)
    fl = flops.prefill_chunk_flops(DIMS, 512, 256) + sum(
        flops.token_flops(DIMS, 1000 + i) for i in range(1, 11))
    assert v["step_mfu"] == pytest.approx(100 * fl / 197e12)


def test_trace_readers_find_nothing_without_a_device_trace():
    rec = _rec(10.0, [11.0, 11.1])
    v = observed.read_all(["device_idle_frac.open", "decode_hbm_roofline",
                           "step_mfu"], _obs([rec]))
    assert v == {}
