"""The Llama path pinned to values read before the harness was split by
architecture: the weights made from a seed, the reference's logits (float32
and the float8 control), the counts of both configurations, and the trace
readers on a hand-made observation.  Each must come out as it did, so
the benchmark's readings do not move with the split."""
import hashlib
import json
import types

import jax
import numpy as np
import pytest

import _paths  # noqa: F401
from _tiny import TINY_MODEL
from bench import cells, flops, observed, reference, spans, trace, weights

SEED = 2**33 + 7
DIGEST = "4a3558f8b606ecf2f17ea08f660be0d379e7d1080b14996167a8952e518f48b7"
TOKENS = (np.arange(37) * 11 + 3) % 256
ROWS = np.arange(33, 37)
COLS = [0, 7, 64, 129, 200, 255]
LOGITS = {
    "float32": ([102, 91, 251, 179], [
        [-0.6549685001373291, -0.21675291657447815, 0.21512578427791595,
         0.17667646706104279, -0.7703427672386169, 2.005835771560669],
        [0.47115179896354675, 0.3468286395072937, 0.9877008199691772,
         0.9427822232246399, -0.28850865364074707, -0.5622833967208862],
        [-0.17916883528232574, -0.5513305068016052, -0.7899721264839172,
         -0.9558457136154175, 0.549915611743927, 0.12887075543403625],
        [-1.297266960144043, -0.3810037076473236, 0.8837887048721313,
         -0.44188907742500305, -0.21956834197044373, 0.011524902656674385]]),
    "fp8": ([102, 91, 251, 179], [
        [-0.8561592102050781, -0.10213851928710938, -0.114532470703125,
         0.26529693603515625, -0.9314117431640625, 1.9268798828125],
        [0.5212326049804688, 0.35405731201171875, 1.02264404296875,
         0.7681198120117188, -0.27197265625, -0.6481170654296875],
        [0.109405517578125, -0.7783126831054688, -1.038726806640625,
         -1.0321502685546875, 0.6995849609375, 0.082275390625],
        [-1.6768646240234375, -0.07631683349609375, 0.96600341796875,
         0.012603759765625, 0.282867431640625, 0.43951416015625]]),
}
COUNTS = {
    "yi-6b": dict(layer_params=2768240640, matmul_params=3030384640,
                  param_count=3292663808, kv_bytes_per_token=32768,
                  weight_bytes=6061309952, token_flops=6322913280.0,
                  prefill_chunk_flops=1460322435072.0),
    "smollm-135m": dict(layer_params=106168320, matmul_params=134479872,
                        param_count=134515008, kv_bytes_per_token=23040,
                        weight_bytes=269100288, token_flops=338079744.0,
                        prefill_chunk_flops=65691648000.0),
}
# the same configurations' K and V bytes and attention FLOPs of a token
# at context 1234, and the weight bytes of a decode step
PER_CONTEXT = {
    "yi-6b": dict(kv_read_bytes=40435712, attn_flops=323485696,
                  step_weight_bytes=6061309952),
    "smollm-135m": dict(kv_read_bytes=28431360, attn_flops=85294080,
                        step_weight_bytes=269100288),
}
READINGS = {"decode_hbm_roofline": 55.18572469022017,
            "step_mfu": 2.5666420569664976,
            "decode_attn_hbm_roofline": 1.15963270694978,
            "loop_host_ms": 9.000000000000005,
            "dispatch_host_ms": 3.000000000000002,
            "device_idle_frac.offline": 0.19999999999999996}


def _dims(name, **over):
    conf = json.loads((cells.ROOT / "bench" / "configs"
                       / f"{name}.json").read_text())
    conf.update(over)
    return cells.model_dims(conf)


@pytest.fixture(scope="module")
def tiny():
    m = _dims("yi-6b", **TINY_MODEL)
    return m, weights.make(m, SEED)


def test_weights_digest(tiny):
    m, params = tiny
    h = hashlib.sha256()
    for x in jax.tree.leaves(params):
        a = np.asarray(x)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    assert h.hexdigest() == DIGEST
    assert weights.param_count(m) == 106816


@pytest.mark.parametrize("precision", ["float32", "fp8"])
def test_reference_logits(tiny, precision):
    m, params = tiny
    z = np.asarray(reference.logits(params, m, TOKENS, ROWS,
                                    precision=precision))
    argmax, vals = LOGITS[precision]
    assert z[:4].argmax(-1).tolist() == argmax
    # float32 rounding of the same program; any change of the arithmetic
    # moves these by far more
    np.testing.assert_allclose(z[:4][:, COLS], vals, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_counts(name):
    m = _dims(name)
    want = COUNTS[name]
    got = dict(layer_params=flops.layer_params(m),
               matmul_params=flops.matmul_params(m),
               param_count=weights.param_count(m),
               kv_bytes_per_token=flops.kv_bytes_per_token(m),
               weight_bytes=flops.weight_bytes(m),
               token_flops=flops.token_flops(m, 1000),
               prefill_chunk_flops=flops.prefill_chunk_flops(m, 512, 256))
    assert got == want


@pytest.mark.parametrize("name", sorted(PER_CONTEXT))
def test_counts_per_context(name):
    m = _dims(name)
    got = dict(kv_read_bytes=flops.kv_read_bytes(m, 1234),
               attn_flops=flops.attn_flops(m, 1234),
               step_weight_bytes=flops.step_weight_bytes(m, 32))
    assert got == PER_CONTEXT[name]
    assert all(type(v) is int for v in got.values())


def test_trace_readers_on_a_fixed_observation():
    m = _dims("yi-6b")
    recs = [types.SimpleNamespace(
        t_due=10.0, prompt=np.zeros(100 + 37 * j, np.int32), t_admit=None,
        aborted=False,
        times=[11.0] + [12.0 + 0.021 * k + 0.001 * j for k in range(1, 30)])
        for j in range(5)]
    tr = trace.Trace(devices=1, window_s=0.5, busy_s=[0.4],
                     programs={"jit_decode_fn": [0.31, 23]},
                     ops={"paged_gqa_decode": 0.0123}, idle_gaps=[])
    sp = []
    for k in range(6):
        t = 0.05 + 0.07 * k
        S = lambda n, s, e, **a: spans.Span(n, t + s, t + e, a,
                                            "engine-loop/3")
        sp += [S("engine.iteration", 0.0, 0.05, queued=0, active=5),
               S("engine.decode", 0.004, 0.05, step=k, rows=5,
                 ctx=700 + 5 * k),
               S("runtime.call", 0.005, 0.049, fn="w0_decode",
                 target="accel" if k != 2 else "host"),
               S("runtime.wait", 0.006, 0.047)]
    tr.spans = sp
    w = types.SimpleNamespace(
        t0=10.0, t1=20.0, records=recs, calls=[[]], stats=[],
        trace=types.SimpleNamespace(t_begin=12.0, t_end=12.5))
    obs = observed.Observed(m, 1, 32, {"bf16_flops": 197e12,
                                       "hbm_bytes_per_s": 819e9}, 1.0, w,
                            [(12.1, 0, 64, 128), (12.3, 0, 0, 200)], tr)
    assert observed.read_all(list(READINGS), obs) == pytest.approx(
        READINGS, rel=1e-12)
