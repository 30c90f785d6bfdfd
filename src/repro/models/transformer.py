"""Unified decoder-only transformer: dense / MoE / VLM / audio families.

Layer weights are stacked along a leading L dim and the layer loop is a
``lax.scan`` (compile time O(1) in depth; enables remat policies).  MoE
uses shard_map expert-parallelism over the ``model`` axis: activations
are replicated across TP, so each model shard routes the same tokens to
*its* experts locally and the combine is a single psum — no all-to-all
required (see DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.model_config import ModelConfig
from repro.models import attention as attn_lib
from repro.models.common import (
    ParamDef, apply_rope, cross_entropy, gelu_mlp, rmsnorm, swiglu,
)
from repro.parallel.mesh import DATA_AXIS, MODEL_AXIS, POD_AXIS, batch_axes
from repro.parallel.sharding import (
    DEFAULT_RULES, ShardingRules, padded_size,
)



# --------------------------------------------------------------- geometry

@dataclasses.dataclass(frozen=True)
class Geometry:
    """TP-padded sizes derived from (config, mesh tp size)."""

    tp: int
    heads: int            # padded query heads
    kv_heads: int         # (unpadded; replicated across TP)
    vocab: int            # padded vocab
    shard_kv: bool        # kv heads TP-shardable

    @staticmethod
    def of(cfg: ModelConfig, tp: int) -> "Geometry":
        hp = padded_size(max(cfg.num_heads, 1), tp)
        vp = padded_size(cfg.vocab_size, tp)
        if cfg.num_heads and cfg.num_kv_heads == cfg.num_heads:
            # MHA: pad KV heads along with Q heads and shard both —
            # replicated K/V projections cost 4.4e14 extra FLOPs/chip on
            # the qwen prefill cell (EXPERIMENTS.md §Perf 3)
            return Geometry(tp=tp, heads=hp, kv_heads=hp, vocab=vp,
                            shard_kv=True)
        return Geometry(tp=tp, heads=hp, kv_heads=cfg.num_kv_heads,
                        vocab=vp, shard_kv=False)


def kv_index_for(cfg: ModelConfig, geom: Geometry):
    """Static q-head -> kv-head map, or None when identity (incl. the
    MHA-padded case where both are padded identically)."""
    if geom.kv_heads == geom.heads:
        return None
    return attn_lib.kv_head_index(cfg.num_heads, cfg.num_kv_heads,
                                  geom.heads)


def make_rules(geom: Geometry, recipe: str = "tp") -> ShardingRules:
    if recipe == "dp":
        # pure data parallelism: batch over every mesh axis, weights and
        # caches replicated; right when the model fits one chip
        rules = dict(DEFAULT_RULES)
        for k in ("vocab", "heads", "kv_heads", "mlp", "experts",
                  "ssm_inner", "ssm_heads", "cache_seq"):
            rules[k] = None
        rules["batch"] = (POD_AXIS, DATA_AXIS, MODEL_AXIS)
        return ShardingRules(rules)
    rules = dict(DEFAULT_RULES)
    if geom.shard_kv:
        # shard the cache on heads instead of sequence (a spec may use
        # each mesh axis once)
        rules["kv_heads"] = MODEL_AXIS
        rules["cache_seq"] = None
    return ShardingRules(rules)


# ------------------------------------------------------------- param defs

def transformer_defs(cfg: ModelConfig, geom: Geometry) -> dict:
    d, L, ff = cfg.d_model, cfg.num_layers, cfg.d_ff
    hd = cfg.resolved_head_dim
    Hp, KV, Vp = geom.heads, geom.kv_heads, geom.vocab
    H = cfg.num_heads

    attn = {
        "wq": ParamDef((L, d, Hp, hd), ("layers", "embed", "heads", "head_dim"),
                       "scaled", mask_dims={2: H}, fan_in=d),
        "wk": ParamDef((L, d, KV, hd), ("layers", "embed", "kv_heads", "head_dim"),
                       "scaled", mask_dims={2: cfg.num_kv_heads}, fan_in=d),
        "wv": ParamDef((L, d, KV, hd), ("layers", "embed", "kv_heads", "head_dim"),
                       "scaled", mask_dims={2: cfg.num_kv_heads}, fan_in=d),
        "wo": ParamDef((L, Hp, hd, d), ("layers", "heads", "head_dim", "embed"),
                       "scaled", mask_dims={1: H}, fan_in=H * hd),
    }
    if cfg.qkv_bias:
        attn["bq"] = ParamDef((L, Hp, hd), ("layers", "heads", "head_dim"),
                              "zeros", mask_dims={1: H})
        attn["bk"] = ParamDef((L, KV, hd), ("layers", "kv_heads", "head_dim"),
                              "zeros", mask_dims={1: cfg.num_kv_heads})
        attn["bv"] = ParamDef((L, KV, hd), ("layers", "kv_heads", "head_dim"),
                              "zeros", mask_dims={1: cfg.num_kv_heads})

    if cfg.family == "moe":
        E = cfg.num_experts
        mlp = {
            "router": ParamDef((L, d, E), ("layers", "embed", None), "scaled"),
            "w_gate": ParamDef((L, E, d, ff),
                               ("layers", "experts", "embed", "expert_mlp"), "scaled"),
            "w_up": ParamDef((L, E, d, ff),
                             ("layers", "experts", "embed", "expert_mlp"), "scaled"),
            "w_down": ParamDef((L, E, ff, d),
                               ("layers", "experts", "expert_mlp", "embed"), "scaled"),
        }
    elif cfg.mlp_type == "swiglu":
        mlp = {
            "w_gate": ParamDef((L, d, ff), ("layers", "embed", "mlp"), "scaled"),
            "w_up": ParamDef((L, d, ff), ("layers", "embed", "mlp"), "scaled"),
            "w_down": ParamDef((L, ff, d), ("layers", "mlp", "embed"), "scaled"),
        }
    else:  # gelu
        mlp = {
            "w_in": ParamDef((L, d, ff), ("layers", "embed", "mlp"), "scaled"),
            "w_out": ParamDef((L, ff, d), ("layers", "mlp", "embed"), "scaled"),
        }

    layers = {
        "attn": attn,
        "mlp": mlp,
        "ln1": ParamDef((L, d), ("layers", "embed"), "ones", dtype="float32"),
        "ln2": ParamDef((L, d), ("layers", "embed"), "ones", dtype="float32"),
    }

    K = max(cfg.num_codebooks, 1)
    if cfg.family == "audio" and K > 1:
        embed = {"table": ParamDef((K, Vp, d), ("codebooks", "vocab", "embed"),
                                   "normal", mask_dims={1: cfg.vocab_size})}
        head = {"w": ParamDef((K, d, Vp), ("codebooks", "embed", "vocab"), "scaled")}
    else:
        embed = {"table": ParamDef((Vp, d), ("vocab", "embed"), "normal",
                                   mask_dims={0: cfg.vocab_size})}
        head = ({} if cfg.tie_embeddings
                else {"w": ParamDef((d, Vp), ("embed", "vocab"), "scaled")})

    return {
        "embed": embed,
        "layers": layers,
        "final_norm": ParamDef((d,), ("embed",), "ones", dtype="float32"),
        "head": head,
    }


# ---------------------------------------------------------------- blocks

def qkv_project(x, lp, cfg: ModelConfig, geom: Geometry, positions):
    """x: (B,S,d) -> q (B,S,Hp,hd), k, v (B,S,KV,hd) with RoPE applied."""
    ap = lp["attn"]
    q = jnp.einsum("bsd,dhk->bshk", x, ap["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, ap["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, ap["wv"])
    if cfg.qkv_bias:
        q = q + ap["bq"]
        k = k + ap["bk"]
        v = v + ap["bv"]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_block(x, lp, cfg: ModelConfig, geom: Geometry, *,
                    positions, mode: str, cache_kv=None, cache_index=None,
                    mesh=None, backend: str = "xla"):
    """Returns (out, (k_new, v_new)).  x: (B,S,d).

    For decode, ``cache_kv`` must ALREADY contain the new token's k/v at
    ``cache_index`` (callers write-then-attend so the token sees itself).
    cfg.attn_impl selects the HOST ("ref") or ACCEL ("flash" Pallas
    kernel) implementation for train/prefill; ``backend="pallas"``
    forces the Pallas kernel regardless of cfg (the per-call ACCEL
    selector the serve engine threads through).
    """
    q, k, v = qkv_project(x, lp, cfg, geom, positions)
    kv_idx = kv_index_for(cfg, geom)
    if mode == "decode":
        k_cache, v_cache = cache_kv
        out = attn_lib.decode_attention(q, k_cache, v_cache, cache_index,
                                        kv_index=kv_idx, backend=backend)
    elif backend == "pallas" or cfg.attn_impl == "flash":
        out = attn_lib.flash_attention_sharded(q, k, v, mesh,
                                               kv_index=kv_idx)
    else:
        out = attn_lib.attention(q, k, v, causal=True, kv_index=kv_idx)
    out = jnp.einsum("bshk,hkd->bsd", out, lp["attn"]["wo"])
    return out, (k, v)


def dense_mlp_block(x, lp, cfg: ModelConfig):
    mp = lp["mlp"]
    if cfg.mlp_type == "swiglu":
        return swiglu(x, mp["w_gate"], mp["w_up"], mp["w_down"])
    return gelu_mlp(x, mp["w_in"], mp["w_out"])


# ------------------------------------------------------------------- MoE

def _local_moe(x_flat, router, w_gate, w_up, w_down, cfg: ModelConfig,
               expert_offset: int, num_experts_total: int, capacity: int):
    """Route T tokens to local experts; returns (partial_out, aux_stats).

    x_flat: (T, d); w_*: (E_loc, ...).  Partial output must be psum'd over
    the model axis by the caller (each shard only applies its experts).
    """
    T, d = x_flat.shape
    E_loc = w_gate.shape[0]
    k = cfg.top_k
    logits = jnp.einsum("td,de->te", x_flat, router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    top_w, top_ids = jax.lax.top_k(probs, k)                     # (T, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)

    # Assignments hitting this shard's experts.
    local = (top_ids >= expert_offset) & (top_ids < expert_offset + E_loc)
    local_ids = jnp.where(local, top_ids - expert_offset, E_loc)  # E_loc = drop bin

    # Position of each assignment within its expert (capacity-limited).
    onehot = jax.nn.one_hot(local_ids, E_loc, dtype=jnp.int32)    # (T, k, E_loc)
    flat_oh = onehot.reshape(T * k, E_loc)
    pos = jnp.cumsum(flat_oh, axis=0) * flat_oh                   # rank+1 where set
    pos_in_expert = (jnp.sum(pos, axis=-1) - 1).reshape(T, k)     # -1 where dropped
    expert_of = local_ids
    keep = local & (pos_in_expert >= 0) & (pos_in_expert < capacity)

    # Scatter tokens into (E_loc, C, d) buffers.
    buf = jnp.zeros((E_loc, capacity, d), x_flat.dtype)
    e_idx = jnp.where(keep, expert_of, 0)
    c_idx = jnp.where(keep, pos_in_expert, 0)
    src = jnp.repeat(x_flat[:, None, :], k, axis=1)               # (T, k, d)
    src = jnp.where(keep[..., None], src, 0)
    buf = buf.at[e_idx.reshape(-1), c_idx.reshape(-1)].add(
        src.reshape(T * k, d), mode="drop")

    # Per-expert FFN.
    if cfg.mlp_type == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
        u = jnp.einsum("ecd,edf->ecf", buf, w_up)
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_down)               # (E_loc, C, d)

    # Combine back to token order with routing weights.
    gathered = out_buf[e_idx.reshape(-1), c_idx.reshape(-1)].reshape(T, k, d)
    w = jnp.where(keep, top_w, 0.0).astype(gathered.dtype)
    y = jnp.sum(gathered * w[..., None], axis=1)                  # (T, d)

    # Switch-style aux load-balance stats (computed on full routing).
    me = jnp.mean(probs, axis=0)                                  # (E,)
    ce = jnp.mean(jax.nn.one_hot(top_ids[:, 0], num_experts_total), axis=0)
    aux = jnp.sum(me * ce) * num_experts_total
    return y, aux


def moe_block(x, lp, cfg: ModelConfig, mesh: Optional[jax.sharding.Mesh]):
    """x: (B,S,d) -> (y, aux_loss)."""
    B, S, d = x.shape
    mp = lp["mlp"]
    E = cfg.num_experts

    if (mesh is None or MODEL_AXIS not in getattr(mesh, "axis_names", ())
            or cfg.sharding_recipe == "dp"):
        cap = max(int(cfg.capacity_factor * B * S * cfg.top_k / E), cfg.top_k)
        y, aux = _local_moe(x.reshape(B * S, d), mp["router"], mp["w_gate"],
                            mp["w_up"], mp["w_down"], cfg, 0, E, cap)
        return y.reshape(B, S, d), aux

    tp = mesh.shape[MODEL_AXIS]
    bdims = batch_axes(mesh)
    dp = int(np.prod([mesh.shape[a] for a in bdims])) if bdims else 1
    E_loc = E // tp
    T_loc = (B // dp) * S
    cap = max(int(cfg.capacity_factor * T_loc * cfg.top_k / E), cfg.top_k)

    def shard_fn(xs, router, w_gate, w_up, w_down):
        T = xs.shape[0] * xs.shape[1]
        idx = jax.lax.axis_index(MODEL_AXIS)
        offset = idx * E_loc
        y, aux = _local_moe(xs.reshape(T, d), router, w_gate, w_up, w_down,
                            cfg, offset, E, cap)
        y = jax.lax.psum(y, MODEL_AXIS)           # combine expert partials
        aux = jax.lax.pmean(aux, MODEL_AXIS)
        if bdims:
            aux = jax.lax.pmean(aux, bdims)
        return y.reshape(xs.shape), aux

    y, aux = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(bdims or None, None, None), P(None, None),
                  P(MODEL_AXIS, None, None), P(MODEL_AXIS, None, None),
                  P(MODEL_AXIS, None, None)),
        out_specs=(P(bdims or None, None, None), P()),
        check_vma=False,
    )(x, mp["router"], mp["w_gate"], mp["w_up"], mp["w_down"])
    return y, aux


# ------------------------------------------------------------ layer body

def layer_body(x, lp, cfg: ModelConfig, geom: Geometry, mesh, *,
               positions, mode: str, cache_kv=None, cache_index=None,
               backend: str = "xla"):
    h, kv = attention_block(rmsnorm(x, lp["ln1"], cfg.norm_eps), lp, cfg, geom,
                            positions=positions, mode=mode,
                            cache_kv=cache_kv, cache_index=cache_index,
                            mesh=mesh, backend=backend)
    x = x + h
    if cfg.family == "moe":
        h, aux = moe_block(rmsnorm(x, lp["ln2"], cfg.norm_eps), lp, cfg, mesh)
    else:
        h = dense_mlp_block(rmsnorm(x, lp["ln2"], cfg.norm_eps), lp, cfg)
        aux = jnp.zeros((), jnp.float32)
    return x + h, kv, aux


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "nothing":
        return fn
    if cfg.remat == "dots":
        # no-batch-dims: saves weight-matmul outputs but RECOMPUTES the
        # (S x S)-shaped attention dots — saving those stacks an
        # O(L*B*S^2) tensor across the layer scan (catastrophic at 4k+)
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


# --------------------------------------------------------------- embed/IO

def embed_inputs(params, batch: dict, cfg: ModelConfig) -> jax.Array:
    table = params["embed"]["table"]
    if cfg.family == "audio" and cfg.num_codebooks > 1:
        # tokens: (B, K, S); sum the K codebook embeddings.
        toks = batch["tokens"]
        x = jnp.zeros(toks.shape[:1] + toks.shape[2:] + (cfg.d_model,),
                      table.dtype)
        for c in range(cfg.num_codebooks):
            x = x + jnp.take(table[c], toks[:, c], axis=0)
        return x
    x = jnp.take(table, batch["tokens"], axis=0)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(x.dtype)
        x = jax.lax.dynamic_update_slice(x, pe, (0, 0, 0))
    return x


def output_logits(params, x, cfg: ModelConfig) -> jax.Array:
    if cfg.family == "audio" and cfg.num_codebooks > 1:
        return jnp.einsum("bsd,kdv->bskv", x, params["head"]["w"])
    w = (params["embed"]["table"].T if cfg.tie_embeddings
         else params["head"]["w"])
    return jnp.einsum("bsd,dv->bsv", x, w)


def lm_loss(logits, batch, cfg: ModelConfig) -> jax.Array:
    if cfg.family == "audio" and cfg.num_codebooks > 1:
        labels = jnp.moveaxis(batch["labels"], 1, 2)      # (B,S,K)
        return cross_entropy(logits, labels, cfg.vocab_size)
    mask = None
    if cfg.family == "vlm":
        S = batch["labels"].shape[1]
        mask = (jnp.arange(S) >= cfg.num_patches)[None, :].astype(jnp.float32)
        mask = jnp.broadcast_to(mask, batch["labels"].shape)
    return cross_entropy(logits, batch["labels"], cfg.vocab_size, mask)


# ------------------------------------------------------------- full model

def _write_kv_layer(stack, new, li, cache_index):
    """Write ``new`` (B,1,KV,hd-or-1) into layer ``li`` of a cache stack
    (L,B,Smax,KV,hd-or-1) at ``cache_index``: a shared scalar position, or
    a (B,) vector of ragged per-row positions (continuous batching)."""
    if cache_index.ndim:
        return jax.vmap(
            lambda c, n, i: jax.lax.dynamic_update_slice(
                c, n[None].astype(c.dtype), (li, i, 0, 0)),
            in_axes=(1, 0, 0), out_axes=1)(stack, new, cache_index)
    return jax.lax.dynamic_update_slice(
        stack, new.astype(stack.dtype)[None], (li, 0, cache_index, 0, 0))


def _write_kv_block(stack, new, li, blk, off):
    """Scatter the new token's KV (B,1,KV,hd) into layer ``li`` of the
    block pool at per-row (physical block, offset).  Rows sharing a
    target (inactive rows all hit junk block 0 offset 0) are benign:
    nothing ever reads the junk block.  A lane-aligned pool (hd padded
    to 128 at allocation) zero-pads the per-token write — cheap, unlike
    padding the whole pool per read."""
    if new.shape[-1] != stack.shape[-1]:
        new = jnp.pad(new, ((0, 0),) * (new.ndim - 1)
                      + ((0, stack.shape[-1] - new.shape[-1]),))
    return stack.at[li, blk, off].set(new[:, 0].astype(stack.dtype))


def forward(params, batch, cfg: ModelConfig, geom: Geometry, mesh, *,
            mode: str, cache: dict | None = None, backend: str = "xla"):
    """mode: train | prefill | decode.

    Decode reads a dense (L,B,Smax,KV,hd) cache, or — when the batch
    carries a ``block_table`` — a paged (L,NB,BS,KV,hd) block pool.
    ``backend`` selects the attention implementation for prefill/decode:
    "xla" (HOST reference) or "pallas" (ACCEL kernels — flash prefill,
    flash-decoding / paged-streaming decode).  A paged int8 pool keeps
    the selector (its ACCEL build is the int8-dequantising paged
    kernel); DENSE int8 decode still ignores it and runs XLA math.
    Returns (logits, new_cache_or_None, aux_loss).
    """
    x = embed_inputs(params, batch, cfg)
    B, S = x.shape[0], x.shape[1]
    if mode == "decode":
        # index: scalar () for position-synchronised decode, or (B,) for
        # ragged continuous-batching decode (each row at its own position)
        idx = batch["index"]
        positions = jnp.broadcast_to(
            idx[:, None] if idx.ndim else idx, (B, S))
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    lp_stack = params["layers"]

    if mode == "decode":
        cache_index = batch["index"]
        kv_idx = kv_index_for(cfg, geom)

        attn_index = (cache_index[:, None, None, None] if cache_index.ndim
                      else cache_index)

        def body(carry, lp):
            x, ck, cv, li, aux = carry
            xn = rmsnorm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = qkv_project(xn, lp, cfg, geom, positions)
            # read the OLD cache, pass the new token explicitly, then write
            # — independent read/write lets XLA alias the carried cache
            # in place instead of copying it per layer (§Perf 2)
            kc = jax.lax.dynamic_index_in_dim(ck, li, 0, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(cv, li, 0, keepdims=False)
            out = attn_lib.decode_attention(
                q, kc.astype(x.dtype), vc.astype(x.dtype), attn_index,
                kv_index=kv_idx, k_new=k, v_new=v, backend=backend)
            ck = _write_kv_layer(ck, k, li, cache_index)
            cv = _write_kv_layer(cv, v, li, cache_index)
            x = x + jnp.einsum("bshk,hkd->bsd", out, lp["attn"]["wo"])
            if cfg.family == "moe":
                h, a = moe_block(rmsnorm(x, lp["ln2"], cfg.norm_eps), lp,
                                 cfg, mesh)
            else:
                h = dense_mlp_block(rmsnorm(x, lp["ln2"], cfg.norm_eps),
                                    lp, cfg)
                a = jnp.zeros((), jnp.float32)
            return (x + h, ck, cv, li + 1, aux + a), None

        if "block_table" in batch:
            return _forward_decode_paged(params, batch, cfg, geom, mesh,
                                         cache, x, positions,
                                         backend=backend)
        if cache["k"].dtype == jnp.int8:
            return _forward_decode_int8(params, batch, cfg, geom, mesh,
                                        cache, x, positions)
        (x, ck, cv, _, aux), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"], jnp.int32(0), jnp.zeros((), jnp.float32)),
            lp_stack)
        new_cache = dict(cache, k=ck, v=cv)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return output_logits(params, x, cfg), new_cache, aux

    def body(x_aux, lp):
        x, aux = x_aux
        x, kv, a = layer_body(x, lp, cfg, geom, mesh, positions=positions,
                              mode=mode, backend=backend)
        if mode == "prefill":
            return (x, aux + a), kv
        return (x, aux + a), None

    body_fn = _remat(body, cfg) if mode == "train" else body
    (x, aux), kvs = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                                 lp_stack)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = output_logits(params, x, cfg)

    new_cache = None
    if mode == "prefill":
        k_all, v_all = kvs  # (L, B, S, KV, hd)
        if cfg.kv_cache_dtype == "int8":
            from repro.models.common import quantize_int8
            kq, ks = quantize_int8(k_all, axis=-1)
            vq, vs = quantize_int8(v_all, axis=-1)
            new_cache = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        else:
            cdt = jnp.dtype(cfg.kv_cache_dtype)
            new_cache = {"k": k_all.astype(cdt), "v": v_all.astype(cdt)}
    return logits, new_cache, aux


def _forward_decode_int8(params, batch, cfg, geom, mesh, cache, x, positions):
    """Decode-layer scan with an int8-quantised KV cache (write-then-attend)."""
    cache_index = batch["index"]
    kv_idx = kv_index_for(cfg, geom)
    from repro.models.common import dequantize_int8, quantize_int8
    attn_index = (cache_index[:, None, None, None] if cache_index.ndim
                  else cache_index)

    def body(carry, lp):
        x, ck, cv, ks, vs, li, aux = carry
        xn = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = qkv_project(xn, lp, cfg, geom, positions)
        # read-old / explicit-new-token / write (aliasing-friendly; §Perf 2)
        kc = dequantize_int8(
            jax.lax.dynamic_index_in_dim(ck, li, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(ks, li, 0, keepdims=False), x.dtype)
        vc = dequantize_int8(
            jax.lax.dynamic_index_in_dim(cv, li, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(vs, li, 0, keepdims=False), x.dtype)
        out = attn_lib.decode_attention(q, kc, vc, attn_index,
                                        kv_index=kv_idx, k_new=k, v_new=v)
        kq, ksc = quantize_int8(k, axis=-1)
        vq, vsc = quantize_int8(v, axis=-1)
        ck = _write_kv_layer(ck, kq, li, cache_index)
        cv = _write_kv_layer(cv, vq, li, cache_index)
        ks = _write_kv_layer(ks, ksc, li, cache_index)
        vs = _write_kv_layer(vs, vsc, li, cache_index)
        x = x + jnp.einsum("bshk,hkd->bsd", out, lp["attn"]["wo"])
        if cfg.family == "moe":
            h, a = moe_block(rmsnorm(x, lp["ln2"], cfg.norm_eps), lp, cfg, mesh)
        else:
            h = dense_mlp_block(rmsnorm(x, lp["ln2"], cfg.norm_eps), lp, cfg)
            a = jnp.zeros((), jnp.float32)
        return (x + h, ck, cv, ks, vs, li + 1, aux + a), None

    (x, ck, cv, ks, vs, _, aux), _ = jax.lax.scan(
        body,
        (x, cache["k"], cache["v"], cache["k_scale"], cache["v_scale"],
         jnp.int32(0), jnp.zeros((), jnp.float32)),
        params["layers"])
    new_cache = {"k": ck, "v": cv, "k_scale": ks, "v_scale": vs}
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return output_logits(params, x, cfg), new_cache, aux


def _forward_decode_paged(params, batch, cfg, geom, mesh, cache, x, positions,
                          backend: str = "xla"):
    """Decode-layer scan over a paged (block-pool) KV cache.

    batch carries ragged per-row state: ``index`` (B,) logical write
    positions and ``block_table`` (B, NBT) physical block ids.  Each
    layer attends the row's blocks in logical order with the
    explicit-new-token path (write-then-attend preserved: the pool
    never contributes the current position — it is masked by ``index``
    — and the new token's KV is passed to attention directly, then
    scattered into the pool).  Math is identical to the dense body; only
    the cache addressing differs, so greedy tokens match byte-for-byte
    when the attention span (NBT * BS) equals the dense max_seq.

    backend="xla" gathers each row's blocks into a logical-order cache
    per layer (HOST); backend="pallas" hands the pool plus the block
    table to the paged decode kernel, which streams the blocks in-kernel
    with no materialised gather (ACCEL).

    An int8 pool (``"k_scale" in cache``) quantises on write — each
    token independently, symmetric over head_dim — scattering q values
    and scales into their parallel pools, and attends over the raw int8
    pool plus scales (HOST dequantises the gathered rows; ACCEL
    dequantises in-kernel).  The current token still enters attention
    at full precision (write-then-attend: it is not read back from the
    pool this step).
    """
    cache_index = batch["index"]                   # (B,)
    table = batch["block_table"]                   # (B, NBT) int32
    bs = cache["k"].shape[2]
    int8 = "k_scale" in cache
    kv_idx = kv_index_for(cfg, geom)
    blk = jnp.take_along_axis(table, (cache_index // bs)[:, None],
                              axis=1)[:, 0]        # (B,) physical block
    off = cache_index % bs
    from repro.models.common import quantize_int8

    def body(carry, lp):
        if int8:
            x, ck, cv, ks, vs, li, aux = carry
        else:
            x, ck, cv, li, aux = carry
        xn = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = qkv_project(xn, lp, cfg, geom, positions)
        kcp = jax.lax.dynamic_index_in_dim(ck, li, 0, keepdims=False)
        vcp = jax.lax.dynamic_index_in_dim(cv, li, 0, keepdims=False)
        if int8:
            out = attn_lib.paged_decode_attention(
                q, kcp, vcp, table, cache_index, k_new=k, v_new=v,
                kv_index=kv_idx, backend=backend,
                k_scale=jax.lax.dynamic_index_in_dim(ks, li, 0,
                                                     keepdims=False),
                v_scale=jax.lax.dynamic_index_in_dim(vs, li, 0,
                                                     keepdims=False))
            kq, ksc = quantize_int8(k, axis=-1)
            vq, vsc = quantize_int8(v, axis=-1)
            ck = _write_kv_block(ck, kq, li, blk, off)
            cv = _write_kv_block(cv, vq, li, blk, off)
            ks = _write_kv_block(ks, ksc, li, blk, off)
            vs = _write_kv_block(vs, vsc, li, blk, off)
        else:
            out = attn_lib.paged_decode_attention(
                q, kcp.astype(x.dtype), vcp.astype(x.dtype), table,
                cache_index, k_new=k, v_new=v, kv_index=kv_idx,
                backend=backend)
            ck = _write_kv_block(ck, k, li, blk, off)
            cv = _write_kv_block(cv, v, li, blk, off)
        x = x + jnp.einsum("bshk,hkd->bsd", out, lp["attn"]["wo"])
        if cfg.family == "moe":
            h, a = moe_block(rmsnorm(x, lp["ln2"], cfg.norm_eps), lp,
                             cfg, mesh)
        else:
            h = dense_mlp_block(rmsnorm(x, lp["ln2"], cfg.norm_eps), lp, cfg)
            a = jnp.zeros((), jnp.float32)
        if int8:
            return (x + h, ck, cv, ks, vs, li + 1, aux + a), None
        return (x + h, ck, cv, li + 1, aux + a), None

    if int8:
        (x, ck, cv, ks, vs, _, aux), _ = jax.lax.scan(
            body,
            (x, cache["k"], cache["v"], cache["k_scale"], cache["v_scale"],
             jnp.int32(0), jnp.zeros((), jnp.float32)),
            params["layers"])
        new_cache = dict(cache, k=ck, v=cv, k_scale=ks, v_scale=vs)
    else:
        (x, ck, cv, _, aux), _ = jax.lax.scan(
            body,
            (x, cache["k"], cache["v"], jnp.int32(0),
             jnp.zeros((), jnp.float32)),
            params["layers"])
        new_cache = dict(cache, k=ck, v=cv)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return output_logits(params, x, cfg), new_cache, aux


def forward_prefill_paged(params, batch, cfg, geom, mesh, cache,
                          backend: str = "xla", attn_fn=None):
    """Chunked prefill over a paged KV cache (prefix caching).

    The chunk's tokens EXTEND a prefix already resident in the block
    pool: batch carries ``tokens`` (B, W) — the uncached span, bucketed
    — plus ``offset`` (B,) first uncached position, ``length`` (B,)
    total feed length, and ``block_table`` (B, NBT) whose first
    ``offset // BS`` entries are the cached (possibly shared) blocks.
    Each layer attends chunk queries at absolute positions
    ``offset + j`` over pool positions [0, offset) plus the chunk
    itself, causally (``attn_lib.paged_prefill_attention``).

    The pool is READ-ONLY here and the chunk's per-layer KV is RETURNED
    (like prefill's cache), not written: matched prefix blocks are
    shared across slots and must not be mutated, so the engine scatters
    the returned chunk KV into the slot's private blocks explicitly.
    Returns (logits (B, W, V), chunk_cache {"k","v"} (L, B, W, KV, hd),
    aux).  With ``offset == 0`` (no cache hit) this degenerates to the
    bucketed dense prefill bit-for-bit: every pool column is masked
    (exact-zero softmax terms), and positions/causality match.

    An int8 pool (``"k_scale" in cache``) dequantises the gathered
    context per layer and the returned chunk cache is quantised
    (``{"k","v","k_scale","v_scale"}``) so the engine's scatter writes
    pool-dtype leaves — note the chunk attends over ROUNDED prefix KV,
    which is exactly why lossy pools sit behind
    ``allow_lossy_prefix_cache`` (serve/README.md tolerance story).

    ``attn_fn`` swaps the per-layer attention implementation (default
    ``attn_lib.paged_prefill_attention``); it must share that ABI.
    ``forward_verify_paged`` uses it to thread the verify-named wrapper
    through the same body.
    """
    if attn_fn is None:
        attn_fn = attn_lib.paged_prefill_attention
    x = embed_inputs(params, batch, cfg)
    B, W = x.shape[0], x.shape[1]
    offset = batch["offset"].astype(jnp.int32)         # (B,)
    length = batch["length"].astype(jnp.int32)         # (B,)
    table = batch["block_table"]                       # (B, NBT)
    positions = offset[:, None] + jnp.arange(W)[None, :]
    kv_idx = kv_index_for(cfg, geom)
    int8 = "k_scale" in cache

    def body(x_aux, xs):
        x, aux = x_aux
        if int8:
            lp, kcp, vcp, kscp, vscp = xs
        else:
            lp, kcp, vcp = xs
            kscp = vscp = None
        xn = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = qkv_project(xn, lp, cfg, geom, positions)
        out = attn_fn(
            q, kcp, vcp, table, offset, length, k_new=k, v_new=v,
            kv_index=kv_idx, backend=backend, k_scale=kscp, v_scale=vscp)
        x = x + jnp.einsum("bshk,hkd->bsd", out, lp["attn"]["wo"])
        if cfg.family == "moe":
            h, a = moe_block(rmsnorm(x, lp["ln2"], cfg.norm_eps), lp,
                             cfg, mesh)
        else:
            h = dense_mlp_block(rmsnorm(x, lp["ln2"], cfg.norm_eps), lp, cfg)
            a = jnp.zeros((), jnp.float32)
        return (x + h, aux + a), (k, v)

    xs = ((params["layers"], cache["k"], cache["v"],
           cache["k_scale"], cache["v_scale"]) if int8
          else (params["layers"], cache["k"], cache["v"]))
    (x, aux), kvs = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    k_all, v_all = kvs                                  # (L, B, W, KV, hd)
    if cfg.kv_cache_dtype == "int8":
        from repro.models.common import quantize_int8
        kq, ks = quantize_int8(k_all, axis=-1)
        vq, vs = quantize_int8(v_all, axis=-1)
        chunk_cache = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    else:
        cdt = jnp.dtype(cfg.kv_cache_dtype)
        chunk_cache = {"k": k_all.astype(cdt), "v": v_all.astype(cdt)}
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return output_logits(params, x, cfg), chunk_cache, aux


def forward_verify_paged(params, batch, cfg, geom, mesh, cache,
                         backend: str = "xla"):
    """Speculative-decode verify forward: score W candidate tokens per
    row in one batched step over the paged pool.

    Identical body to ``forward_prefill_paged`` — verify IS chunk
    prefill at offset (batch: ``tokens`` (B, W) = the fed candidates,
    ``offset`` (B,) committed position, ``length`` (B,) =
    ``offset + n_valid``, ``block_table``) — but attention routes
    through ``attn_lib.paged_verify_attention`` so the ACCEL build hits
    the verify-named Pallas wrapper and the runtime accounts verify
    calls separately from chunked prefill.  Returns the full
    (logits (B, W, V), chunk_cache (L, B, W, KV, hd), aux) triple; the
    caller samples every column (positions ``offset + 1 + j``) and
    scatters only the accepted prefix's KV.
    """
    return forward_prefill_paged(params, batch, cfg, geom, mesh, cache,
                                 backend=backend,
                                 attn_fn=attn_lib.paged_verify_attention)
