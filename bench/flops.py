"""Operations and bytes a served token needs, from the model's shapes.

Counts are of the work the algorithm requires, not of what a build
happens to do: bucket padding, lane padding of the pool and recomputed
values are left out, so a share computed from them cannot pass 100%
unless the time leaves out part of the work.
"""
from __future__ import annotations


def layer_params(m: dict) -> int:
    """Weights of every layer's projections and MLP."""
    d, L, ff = m["d_model"], m["num_layers"], m["d_ff"]
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    return L * (d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff)


def matmul_params(m: dict) -> int:
    """Weights a generated token multiplies against: the layers plus the
    output head (the embedding is a row lookup)."""
    return layer_params(m) + m["d_model"] * m["vocab_size"]


def token_flops(m: dict, context: int) -> float:
    """FLOPs of one token at a position that attends ``context`` keys
    (itself included): 2 per weight, plus QK^T and PV over the context."""
    attn = 4 * m["num_layers"] * m["num_heads"] * m["head_dim"] * context
    return 2.0 * matmul_params(m) + attn


def prefill_chunk_flops(m: dict, offset: int, n: int) -> float:
    """Tokens ``offset .. offset + n - 1`` of one prompt.  The output
    head is not counted: a prompt token's logits are not needed, and the
    one that samples the first token is a rounding error."""
    L, H, hd = m["num_layers"], m["num_heads"], m["head_dim"]
    # sum over positions p of (p + 1) attended keys
    keys = n * offset + n * (n + 1) // 2
    return 2.0 * layer_params(m) * n + 4.0 * L * H * hd * keys


def kv_bytes_per_token(m: dict, dtype_bytes: int = 2) -> int:
    """K and V of one position over all layers, at the real head_dim."""
    return 2 * m["num_layers"] * m["num_kv_heads"] * m["head_dim"] * dtype_bytes


def weight_bytes(m: dict, dtype_bytes: int = 2) -> int:
    """Weights one decode step must read: every layer and the head
    (norm weights are float32; the embedding rows read are negligible)."""
    d, L = m["d_model"], m["num_layers"]
    return matmul_params(m) * dtype_bytes + (2 * L + 1) * d * 4

