"""The configurations: each loads by name, keeps the published widths,
and a two-layer cut serves a request through the engine (Pallas in
interpret mode on the CPU)."""
import dataclasses
import json

import numpy as np
import pytest

import _paths  # noqa: F401
from bench import cells, serve, weights

BENCH = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
# from each model's published config.json
PUBLISHED = {
    "yi-6b": {"d_model": 4096, "num_heads": 32, "num_kv_heads": 4,
              "head_dim": 128, "d_ff": 11008, "vocab_size": 64000,
              "rope_theta": 5_000_000.0, "norm_eps": 1e-5,
              "tie_embeddings": False, "num_layers": 32},
    "smollm-135m": {"d_model": 576, "num_heads": 9, "num_kv_heads": 3,
                    "head_dim": 64, "d_ff": 1536, "vocab_size": 49152,
                    "rope_theta": 10_000.0, "norm_eps": 1e-5,
                    "tie_embeddings": True, "num_layers": 30},
}
KEYS = {"num_layers": "num_hidden_layers"}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_loads_by_name(cell):
    c = cells.load(cell)
    assert c.chips in (1, 4)
    assert c.end_to_end and c.per_layer
    assert any(m["name"] == "setup_s" for m in c.end_to_end)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_published_widths(entry):
    conf = json.loads((cells.ROOT / entry["file"]).read_text())
    assert conf["source"] == entry["source"]
    dims = cells.model_dims(conf)
    want = PUBLISHED[entry["name"]]
    changed = {k for k, v in want.items() if dims[k] != v}
    # only the keys BENCHMARK.json lists as reduced differ, and no width
    assert {KEYS.get(k, k) for k in changed} == set(entry["reduced"])
    assert set(conf["reduced"]) == set(entry["reduced"])
    assert changed <= {"num_layers"}


@pytest.mark.parametrize("name", ["yi-6b", "smollm-135m"])
def test_two_layer_cut_serves_one_request(name):
    from repro.core.policy import PinAccel
    from repro.serve import (ContinuousBatchingEngine, GenerationRequest,
                             SamplingParams)
    conf = json.loads((cells.ROOT / "bench" / "configs"
                       / f"{name}.json").read_text())
    dims = dict(cells.model_dims(conf), num_layers=2)
    params = weights.make(dims, seed=2**35 + 1)
    eng_kw = dict(conf["engine"], max_slots=2, max_seq=256)
    eng = ContinuousBatchingEngine(serve.model_config(name, dims),
                                   params=params, policy=PinAccel(), **eng_kw)
    prompt = np.arange(40, dtype=np.int32) * 7 % dims["vocab_size"]
    req = GenerationRequest(prompt, max_new_tokens=3,
                            sampling=SamplingParams())
    out = eng.run([req])[req.req_id]
    assert out.finish_reason == "length"
    toks = np.asarray(out.tokens)
    assert toks.shape == (3,) and 0 <= toks.min() and toks.max() < dims["vocab_size"]
    assert eng.stats["prefill_tokens"] == 40


def test_reduced_and_assumed_are_documented():
    for entry in BENCH["configs"]:
        conf = json.loads((cells.ROOT / entry["file"]).read_text())
        for key in entry["reduced"]:
            assert conf["reduced"][key]["why"]
        assert conf["assumed"] and conf["deployment"]
        assert dataclasses.asdict(serve.model_config(
            entry["name"], cells.model_dims(conf)))
