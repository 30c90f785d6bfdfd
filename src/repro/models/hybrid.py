"""Zamba2-style hybrid: Mamba2 backbone + *shared* attention blocks.

One set of attention+MLP weights is re-applied every ``attn_every``
layers [arXiv:2411.15242].  Because weights are shared, the layer loop
stays a lax.scan over stacked Mamba params; the shared block is invoked
under ``lax.cond`` on a per-layer flag, so non-attention layers pay no
attention FLOPs.  Each invocation sees different activations, so decode
keeps ``n_inv = L // attn_every`` separate KV cache slots, indexed by a
running counter carried through the scan.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.model_config import ModelConfig
from repro.models.common import ParamDef, rmsnorm
from repro.models.ssm import mamba_mix, ssm_defs
from repro.models.transformer import Geometry, attention_block, dense_mlp_block


def num_attn_invocations(cfg: ModelConfig) -> int:
    return cfg.num_layers // cfg.attn_every


def attn_layer_flags(cfg: ModelConfig) -> np.ndarray:
    """flags[l] = 1 where the shared attention block runs (after mamba)."""
    flags = np.zeros(cfg.num_layers, np.int32)
    flags[cfg.attn_every - 1::cfg.attn_every] = 1
    return flags


def hybrid_defs(cfg: ModelConfig, geom: Geometry) -> dict:
    d, ff, hd = cfg.d_model, cfg.d_ff, cfg.resolved_head_dim
    Hp, KV = geom.heads, geom.kv_heads
    H = cfg.num_heads
    shared = {
        "attn": {
            "wq": ParamDef((d, Hp, hd), ("embed", "heads", "head_dim"),
                           "scaled", mask_dims={1: H}, fan_in=d),
            "wk": ParamDef((d, KV, hd), ("embed", "kv_heads", "head_dim"),
                           "scaled", mask_dims={1: cfg.num_kv_heads},
                           fan_in=d),
            "wv": ParamDef((d, KV, hd), ("embed", "kv_heads", "head_dim"),
                           "scaled", mask_dims={1: cfg.num_kv_heads},
                           fan_in=d),
            "wo": ParamDef((Hp, hd, d), ("heads", "head_dim", "embed"),
                           "scaled", mask_dims={0: H}, fan_in=H * hd),
        },
        "mlp": {
            "w_gate": ParamDef((d, ff), ("embed", "mlp"), "scaled"),
            "w_up": ParamDef((d, ff), ("embed", "mlp"), "scaled"),
            "w_down": ParamDef((ff, d), ("mlp", "embed"), "scaled"),
        },
        "ln1": ParamDef((d,), ("embed",), "ones", dtype="float32"),
        "ln2": ParamDef((d,), ("embed",), "ones", dtype="float32"),
    }
    return {"mamba": ssm_defs(cfg), "shared": shared}


def shared_block(x, sp, cfg: ModelConfig, geom: Geometry, *, positions,
                 mode: str, cache_kv=None, cache_index=None):
    lp = {"attn": sp["attn"]}
    h, kv = attention_block(rmsnorm(x, sp["ln1"], cfg.norm_eps), lp, cfg, geom,
                            positions=positions, mode=mode,
                            cache_kv=cache_kv, cache_index=cache_index)
    x = x + h
    h = dense_mlp_block(rmsnorm(x, sp["ln2"], cfg.norm_eps),
                        {"mlp": sp["mlp"]}, cfg)
    return x + h, kv


def _noop_branch(args):
    x, ak, av, slot = args
    return x, ak, av, slot


def hybrid_forward_core(params, x, cfg: ModelConfig, geom: Geometry, mesh, *,
                        mode: str, positions, cache: dict | None):
    """Scan over mamba layers with conditional shared-attn invocations.

    cache layout (decode input / prefill output):
      conv_x/conv_B/conv_C: (L, B, C, K-1), ssd: (L, B, nh, hd, ns),
      attn_k/attn_v: (n_inv, B, Smax, KV, hd)
    Decode additionally reads batch-level "index" via ``cache_index``.
    Returns (x, new_cache_or_None).
    """
    flags = jnp.asarray(attn_layer_flags(cfg))
    sp, mp = params["shared"], params["mamba"]
    B, S = x.shape[0], x.shape[1]
    n_inv = num_attn_invocations(cfg)
    hd = cfg.resolved_head_dim
    decode = mode == "decode"
    cache_index = cache.get("index") if (cache is not None and decode) else None

    def attn_branch(args):
        x, ak, av, slot = args
        if decode:
            from repro.models import attention as attn_lib
            from repro.models.transformer import qkv_project
            xn = rmsnorm(x, sp["ln1"], cfg.norm_eps)
            q, k, v = qkv_project(xn, {"attn": sp["attn"]}, cfg, geom,
                                  positions)
            # read-old / explicit-new-token / write (aliasing; §Perf 2)
            kc = jax.lax.dynamic_index_in_dim(ak, slot, 0, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(av, slot, 0, keepdims=False)
            from repro.models.transformer import kv_index_for
            kv_idx = kv_index_for(cfg, geom)
            out = attn_lib.decode_attention(
                q, kc.astype(x.dtype), vc.astype(x.dtype), cache_index,
                kv_index=kv_idx, k_new=k, v_new=v)
            ak = jax.lax.dynamic_update_slice(
                ak, k.astype(ak.dtype)[None], (slot, 0, cache_index, 0, 0))
            av = jax.lax.dynamic_update_slice(
                av, v.astype(av.dtype)[None], (slot, 0, cache_index, 0, 0))
            x = x + jnp.einsum("bshk,hkd->bsd", out, sp["attn"]["wo"])
            h = dense_mlp_block(rmsnorm(x, sp["ln2"], cfg.norm_eps),
                                {"mlp": sp["mlp"]}, cfg)
            x = x + h
        else:
            x, (k, v) = shared_block(x, sp, cfg, geom, positions=positions,
                                     mode=mode)
            if mode == "prefill":
                ak = jax.lax.dynamic_update_slice(
                    ak, k.astype(ak.dtype)[None], (slot, 0, 0, 0, 0))
                av = jax.lax.dynamic_update_slice(
                    av, v.astype(av.dtype)[None], (slot, 0, 0, 0, 0))
        return x, ak, av, slot + 1

    # Attention-cache buffers (carried through the scan).
    if decode:
        ak, av = cache["attn_k"], cache["attn_v"]
    elif mode == "prefill":
        ak = jnp.zeros((n_inv, B, S, geom.kv_heads, hd), x.dtype)
        av = jnp.zeros_like(ak)
    else:  # train: dummies (cond still needs uniform signatures)
        ak = jnp.zeros((max(n_inv, 1), B, 1, geom.kv_heads, hd), x.dtype)
        av = jnp.zeros_like(ak)

    if decode:
        def body(carry, per_layer):
            x, ak, av, slot, ssd_st, cx, cb, cc = carry
            lp, flag, li = per_layer
            conv_l = tuple(
                jax.lax.dynamic_index_in_dim(c, li, 0, keepdims=False)
                for c in (cx, cb, cc))
            ssd_l = jax.lax.dynamic_index_in_dim(ssd_st, li, 0, keepdims=False)
            h, (ncv, nssd) = mamba_mix(
                rmsnorm(x, lp["ln"], cfg.norm_eps), lp, cfg, mode="decode",
                conv_state=conv_l, ssd_state=ssd_l)
            cx, cb, cc = (
                jax.lax.dynamic_update_slice(c, n.astype(c.dtype)[None],
                                             (li, 0, 0, 0))
                for c, n in zip((cx, cb, cc), ncv))
            ssd_st = jax.lax.dynamic_update_slice(
                ssd_st, nssd[None].astype(ssd_st.dtype), (li, 0, 0, 0, 0))
            x = x + h
            x, ak, av, slot = jax.lax.cond(flag > 0, attn_branch, _noop_branch,
                                           (x, ak, av, slot))
            return (x, ak, av, slot, ssd_st, cx, cb, cc), None

        carry0 = (x, ak, av, jnp.int32(0), cache["ssd"],
                  cache["conv_x"], cache["conv_B"], cache["conv_C"])
        per_layer = (mp, flags, jnp.arange(cfg.num_layers, dtype=jnp.int32))
        (x, ak, av, _, ssd_st, cx, cb, cc), _ = jax.lax.scan(
            body, carry0, per_layer)
        new_cache = dict(cache, attn_k=ak, attn_v=av, ssd=ssd_st,
                         conv_x=cx, conv_B=cb, conv_C=cc)
        return x, new_cache

    def body(carry, per_layer):
        x, ak, av, slot = carry
        lp, flag = per_layer
        h, (ncv, nssd) = mamba_mix(rmsnorm(x, lp["ln"], cfg.norm_eps), lp, cfg,
                                   mode=mode)
        x = x + h
        x, ak, av, slot = jax.lax.cond(flag > 0, attn_branch, _noop_branch,
                                       (x, ak, av, slot))
        if mode == "prefill":
            ys = (ncv[0].astype(x.dtype), ncv[1].astype(x.dtype),
                  ncv[2].astype(x.dtype), nssd)
        else:
            ys = None
        return (x, ak, av, slot), ys

    (x, ak, av, _), ys = jax.lax.scan(body, (x, ak, av, jnp.int32(0)),
                                      (mp, flags))
    if mode == "prefill":
        cx, cb, cc, ssd_st = ys
        new_cache = {"conv_x": cx, "conv_B": cb, "conv_C": cc, "ssd": ssd_st,
                     "attn_k": ak, "attn_v": av}
        return x, new_cache
    return x, None
