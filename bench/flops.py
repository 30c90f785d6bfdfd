"""Operations and bytes a served token needs, from the model's shapes,
by the module of its architecture (``bench/arch``).

Counts are of the work the algorithm requires, not of what a build
happens to do: bucket padding, lane padding of the pool and recomputed
values are left out, so a share computed from them cannot pass 100%
unless the time leaves out part of the work.
"""
from __future__ import annotations

from bench import arch


def _arch(m: dict):
    return arch.load(m["model_type"])


def token_flops(m: dict, context: int) -> float:
    """FLOPs of one generated token at a position that attends
    ``context`` keys (itself included)."""
    return _arch(m).token_flops(m, context)


def prefill_chunk_flops(m: dict, offset: int, n: int) -> float:
    """FLOPs of tokens ``offset .. offset + n - 1`` of one prompt."""
    return _arch(m).prefill_chunk_flops(m, offset, n)


def attn_flops(m: dict, ctx: int) -> float:
    """Attention FLOPs of one token at context ``ctx``."""
    return _arch(m).attn_flops(m, ctx)


def kv_read_bytes(m: dict, ctx: int) -> int:
    """K and V bytes a token reads at context ``ctx`` (with ``ctx`` 1:
    the bytes one new position writes)."""
    return _arch(m).kv_read_bytes(m, ctx)


def step_weight_bytes(m: dict, rows) -> int:
    """Weight bytes one decode step of ``rows`` rows must read."""
    return _arch(m).step_weight_bytes(m, rows)


def layer_params(m: dict) -> int:
    """Weights of every layer's projections and MLP."""
    return _arch(m).layer_params(m)


def matmul_params(m: dict) -> int:
    """Weights a generated token multiplies against."""
    return _arch(m).matmul_params(m)


def kv_bytes_per_token(m: dict) -> int:
    """K and V of one position over all layers."""
    return _arch(m).kv_bytes_per_token(m)


def weight_bytes(m: dict) -> int:
    """Every weight a decode step can read, in the served types."""
    return _arch(m).weight_bytes(m)
