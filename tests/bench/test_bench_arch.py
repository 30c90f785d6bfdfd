"""Architectures as files: a second architecture (``arch_fixture/toy.py``,
its configuration and a cell in the form of ``BENCHMARK.json``) runs
through the harness with no edit to a generic file; a ``model_type``
with no module is refused by name; and the Llama reference imports
nothing of the program."""
import json
import os
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

import _paths  # noqa: F401
from bench import arch, cells, check, observed, serve, trace, weights

FIXTURE = Path(__file__).resolve().parent / "arch_fixture"
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture
def toy(monkeypatch):
    """The fixture's cell, with ``ARCH_DIR`` pointed at the fixture."""
    monkeypatch.setattr(arch, "ARCH_DIR", FIXTURE)
    return cells.load("toy.shortdoc", root=FIXTURE, bench_file="bench.json")


def test_toy_config_is_not_llamas(toy):
    assert toy.dims["model_type"] == "toy"
    assert "rope_theta" not in toy.config and "hidden_size" not in toy.config
    assert [m["name"] for m in toy.per_layer] == ["step_mfu"]


def test_toy_serves_and_checks(toy):
    from repro.core.policy import PinAccel
    from repro.serve import (ContinuousBatchingEngine, GenerationRequest,
                             SamplingParams)
    m = toy.dims
    params = weights.make(m, seed=2**33 + 3)
    assert "w_in" in params["layers"]["mlp"] and "bq" in params["layers"]["attn"]
    eng = ContinuousBatchingEngine(serve.model_config(toy.config_name, m),
                                   params=params, policy=PinAccel(),
                                   **toy.config["engine"])
    prompt = (np.arange(40, dtype=np.int32) * 7 + 1) % m["vocab_size"]
    req = GenerationRequest(prompt, max_new_tokens=6,
                            sampling=SamplingParams())
    out = eng.run([req])[req.req_id]
    rec = serve.Record(req.req_id, 0.0, 0.0, prompt, 6, True, 0, None,
                       tokens=[int(t) for t in out.tokens])
    lim = toy.config["check"]
    recs = check.sample([rec], 1, lim["sample_tokens"], lim["sample_requests"])
    assert recs == [rec]
    res = check.compare(params, m, recs)
    assert res["tokens"] == 6
    assert res["max_logit_gap"] <= lim["max_logit_gap"]
    # one wrong token, planted where it is produced
    rec.tokens[-1] = (rec.tokens[-1] + 1) % m["vocab_size"]
    assert check.compare(params, m, [rec])["max_logit_gap"] > \
        lim["max_logit_gap"]


def test_toy_step_mfu_by_hand(toy):
    m = toy.dims
    L, H, hd, d, V, ff, KV = (m["num_layers"], m["num_heads"], m["head_dim"],
                              m["d_model"], m["vocab_size"], m["d_ff"],
                              m["num_kv_heads"])
    layer = L * (2 * d * H * hd + 2 * d * KV * hd + 2 * d * ff)
    # one request of a 50-token prompt decoding 4 tokens in the span, and
    # one chunk of 16 tokens at offset 32
    rec = types.SimpleNamespace(t_due=10.0, times=[11.0, 12.1, 12.2, 12.3,
                                                   12.4, 13.5],
                                prompt=np.zeros(50, np.int32), t_admit=None,
                                aborted=False)
    tr = trace.Trace(devices=1, window_s=1.0, busy_s=[0.5], programs={},
                     ops={}, idle_gaps=[])
    w = types.SimpleNamespace(t0=10.0, t1=20.0, records=[rec], calls=[[]],
                              stats=[], trace=types.SimpleNamespace(
                                  t_begin=12.0, t_end=13.0))
    obs = observed.Observed(m, 1, 2, PEAKS, 1.0, w, [(12.05, 0, 32, 16)], tr)
    keys = sum(32 + j + 1 for j in range(16))
    fl = 2 * layer * 16 + 4 * L * H * hd * keys
    fl += sum(2 * (layer + d * V) + 4 * L * H * hd * (50 + i)
              for i in range(1, 5))
    assert observed.read_all(["step_mfu"], obs)["step_mfu"] == \
        pytest.approx(100 * fl / 1e12)


def test_unknown_model_type_names_the_missing_file(tmp_path, monkeypatch):
    monkeypatch.setattr(arch, "ARCH_DIR", tmp_path)
    with pytest.raises(FileNotFoundError, match="no_such_arch.py"):
        cells.model_dims({"model_type": "no_such_arch"})
    with pytest.raises(ValueError, match="not a module name"):
        arch.load("../llama")


def test_dims_must_be_hashable(tmp_path, monkeypatch):
    (tmp_path / "listy.py").write_text(
        "def dims(c):\n    return {'layer_types': ['full', 'window']}\n")
    monkeypatch.setattr(arch, "ARCH_DIR", tmp_path)
    with pytest.raises(TypeError, match="hashable"):
        cells.model_dims({"model_type": "listy"})


def test_llama_reference_imports_nothing_of_the_program():
    code = (
        "import json, sys\n"
        "sys.path[:0] = ['tests/bench']\n"
        "from _tiny import TINY_MODEL\n"
        "from bench import cells, flops, reference, weights\n"
        "conf = json.loads(open('bench/configs/yi-6b.json').read())\n"
        "conf.update(TINY_MODEL)\n"
        "m = cells.model_dims(conf)\n"
        "z = reference.logits(weights.make(m, 5), m, list(range(1, 30)),\n"
        "                     [27, 28])\n"
        "assert z.shape == (reference.OUT_ROWS, m['vocab_size'])\n"
        "assert flops.token_flops(m, 10) > 0\n"
        "print(json.dumps(sorted(k for k in sys.modules\n"
        "                        if k.split('.')[0] == 'repro')))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(_paths.ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], cwd=_paths.ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) == []
