"""Common model building blocks (pure JAX, no flax).

Parameters are plain nested dicts of jnp arrays.  Each model module
defines a parallel tree of ``ParamDef`` (shape + logical axes + init),
from which we derive both the materialised params and their
PartitionSpecs.  Padded dimensions (heads/vocab made TP-divisible) are
zero-masked at init so they contribute exactly zero to fwd/bwd.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import ShardingRules, DEFAULT_RULES


@dataclasses.dataclass
class ParamDef:
    shape: tuple[int, ...]
    logical: tuple[Optional[str], ...]
    init: str = "normal"            # normal | zeros | ones | scaled
    scale: float = 0.02
    # mask_dims: {dim_index: valid_size} -> zero out the padded tail
    mask_dims: dict[int, int] = dataclasses.field(default_factory=dict)
    dtype: Optional[str] = None     # override model dtype (e.g. fp32 norms)
    # "scaled" init's fan-in when the contracted input is not the
    # second-to-last dim (attention projections: (…, d, heads, hd) in,
    # (…, heads, hd, d) out)
    fan_in: Optional[int] = None

    def spec(self, rules: ShardingRules) -> P:
        return rules.spec(*self.logical)


def _init_array(key, d: ParamDef, dtype) -> jax.Array:
    dt = jnp.dtype(d.dtype) if d.dtype else dtype
    if d.init == "zeros":
        x = jnp.zeros(d.shape, dt)
    elif d.init == "ones":
        x = jnp.ones(d.shape, dt)
    else:
        scale = d.scale
        if d.init == "scaled":  # 1/sqrt(fan_in), default: 2nd-to-last dim
            fan_in = d.fan_in or (d.shape[-2] if len(d.shape) >= 2
                                  else d.shape[-1])
            scale = 1.0 / math.sqrt(fan_in)
        x = (jax.random.normal(key, d.shape, jnp.float32) * scale).astype(dt)
    for dim, valid in d.mask_dims.items():
        if valid < d.shape[dim]:
            mask = (jnp.arange(d.shape[dim]) < valid).astype(x.dtype)
            mask = mask.reshape([-1 if i == dim else 1 for i in range(x.ndim)])
            x = x * mask
    return x


def init_params(key, defs, dtype) -> dict:
    """Materialise a nested dict of ParamDef -> arrays."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    arrs = [_init_array(k, d, dtype) for k, d in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, arrs)


def param_specs(defs, rules: ShardingRules | None = None) -> dict:
    rules = rules or ShardingRules(DEFAULT_RULES)
    return jax.tree.map(
        lambda d: d.spec(rules), defs, is_leaf=lambda x: isinstance(x, ParamDef)
    )


def param_shapes(defs, dtype) -> dict:
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype) if d.dtype else dtype),
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


# ---------------------------------------------------------------- layers

def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                    # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                    # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down)


def gelu_mlp(x, w_in, w_out, b_in=None, b_out=None):
    h = jnp.einsum("...d,df->...f", x, w_in)
    if b_in is not None:
        h = h + b_in
    h = jax.nn.gelu(h)
    o = jnp.einsum("...f,fd->...d", h, w_out)
    if b_out is not None:
        o = o + b_out
    return o


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  vocab_valid: int, mask: jax.Array | None = None) -> jax.Array:
    """Mean next-token CE; padded vocab columns are excluded."""
    v = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if vocab_valid < v:
        pad_bias = jnp.where(jnp.arange(v) < vocab_valid, 0.0, -1e9)
        logits = logits + pad_bias
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def quantize_int8(x: jax.Array, axis: int = -1):
    """Symmetric per-slice int8 quantisation -> (q, scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)
