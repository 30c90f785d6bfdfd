"""Operations and bytes from shapes, checked against hand counts for
both configurations."""
import json

import pytest

import _paths  # noqa: F401
from bench import cells, flops, peaks, weights


def _dims(name):
    return cells.model_dims(json.loads(
        (cells.ROOT / "bench" / "configs" / f"{name}.json").read_text()))


def test_yi6b_counts():
    m = _dims("yi-6b")
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert flops.layer_params(m) == 16 * per_layer == 2_768_240_640
    assert flops.matmul_params(m) == 2_768_240_640 + 4096 * 64000
    # embedding + head + layers + norms: the 3.29 B of one pipeline stage
    assert weights.param_count(m) == 3_292_663_808
    assert flops.kv_bytes_per_token(m) == 32_768
    assert flops.token_flops(m, 1000) == 2 * 3_030_384_640 + 4 * 16 * 32 * 128 * 1000
    # 256 tokens at offset 512 attend 512 + 1 .. 512 + 256 keys
    keys = sum(512 + j + 1 for j in range(256))
    assert flops.prefill_chunk_flops(m, 512, 256) == \
        2 * 2_768_240_640 * 256 + 4 * 16 * 32 * 128 * keys
    assert flops.weight_bytes(m) == 3_030_384_640 * 2 + 33 * 4096 * 4


def test_smollm135m_counts():
    m = _dims("smollm-135m")
    assert weights.param_count(m) == 134_515_008      # SmolLM-135M
    assert flops.matmul_params(m) == 30 * 3_538_944 + 576 * 49152
    # real head_dim: the pool's lane padding is not work
    assert flops.kv_bytes_per_token(m) == 2 * 30 * 3 * 64 * 2


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
