"""Gradient compression with error feedback for cross-pod DP reduce.

int8 symmetric quantisation per-tensor with an error-feedback buffer
(1-bit-Adam-family trick): e' = g + e - deQ(Q(g + e)); the quantised
values are what crosses the slow inter-pod links.  Used by the train
step when ``TrainConfig.grad_compression == "int8_ef"``: intra-pod
reduction stays fp32 (fast ICI), the pod-axis reduction runs on the
compressed representation inside a shard_map.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.parallel.mesh import POD_AXIS


def compress_int8_ef(grads: Any, err: Any) -> tuple[Any, Any, Any]:
    """Returns (q_grads int8, scales, new_err)."""
    def one(g, e):
        x = g.astype(jnp.float32) + e
        amax = jnp.max(jnp.abs(x))
        scale = jnp.maximum(amax, 1e-12) / 127.0
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        deq = q.astype(jnp.float32) * scale
        return q, scale, x - deq

    flat, tree = jax.tree.flatten(grads)
    eflat = jax.tree.leaves(err)
    out = [one(g, e) for g, e in zip(flat, eflat)]
    return (jax.tree.unflatten(tree, [o[0] for o in out]),
            jax.tree.unflatten(tree, [o[1] for o in out]),
            jax.tree.unflatten(tree, [o[2] for o in out]))


def decompress_int8(q: Any, scales: Any) -> Any:
    return jax.tree.map(
        lambda qq, s: qq.astype(jnp.float32) * s, q, scales)


def pod_allreduce_compressed(grads: Any, err: Any) -> tuple[Any, Any]:
    """Inside a shard_map block that is manual over POD_AXIS: mean-reduce
    grads across pods in int8 (int32 accumulation), with error feedback.

    Bandwidth on the pod links: 1 byte/element (+1 scalar) vs 4.
    """
    q, scales, new_err = compress_int8_ef(grads, err)
    npods = jax.lax.axis_size(POD_AXIS)

    def reduce_one(qq, s):
        tot = jax.lax.psum(qq.astype(jnp.int32), POD_AXIS)
        s_max = jax.lax.pmax(s, POD_AXIS)   # conservative shared scale
        return tot.astype(jnp.float32) * s_max / npods

    mean = jax.tree.map(reduce_one, q, scales)
    return mean, new_err
