"""Llama-architecture decoders (Yi, SmolLM): RMSNorm, rotary embeddings
on the two halves of each head, grouped-query attention, SwiGLU MLP,
every layer alike.  Configuration keys are Hugging Face's."""
from __future__ import annotations

import math

import numpy as np

from bench.weights import EMBED_STD


def dims(c: dict) -> dict:
    """Model dimensions from a configuration file (Hugging Face keys)."""
    H = c["num_attention_heads"]
    return {"d_model": c["hidden_size"], "num_layers": c["num_hidden_layers"],
            "d_ff": c["intermediate_size"], "vocab_size": c["vocab_size"],
            "num_heads": H, "num_kv_heads": c["num_key_value_heads"],
            "head_dim": c.get("head_dim") or c["hidden_size"] // H,
            "rope_theta": float(c["rope_theta"]),
            "norm_eps": float(c["rms_norm_eps"]),
            "tie_embeddings": bool(c["tie_word_embeddings"])}


def shapes(m: dict) -> dict:
    """(shape, dtype, init std or 'norm') for every leaf of the tree."""
    d, L, ff, V = m["d_model"], m["num_layers"], m["d_ff"], m["vocab_size"]
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    bf, f32 = "bfloat16", "float32"
    tree = {
        "embed": {"table": ((V, d), bf, EMBED_STD)},
        "layers": {
            "attn": {"wq": ((L, d, H, hd), bf, 1 / math.sqrt(d)),
                     "wk": ((L, d, KV, hd), bf, 1 / math.sqrt(d)),
                     "wv": ((L, d, KV, hd), bf, 1 / math.sqrt(d)),
                     "wo": ((L, H, hd, d), bf, 1 / math.sqrt(H * hd))},
            "mlp": {"w_gate": ((L, d, ff), bf, 1 / math.sqrt(d)),
                    "w_up": ((L, d, ff), bf, 1 / math.sqrt(d)),
                    "w_down": ((L, ff, d), bf, 1 / math.sqrt(ff))},
            "ln1": ((L, d), f32, "norm"),
            "ln2": ((L, d), f32, "norm"),
        },
        "final_norm": ((d,), f32, "norm"),
        "head": ({} if m["tie_embeddings"]
                 else {"w": ((d, V), bf, 1 / math.sqrt(d))}),
    }
    return tree


def program_config(name: str, m: dict):
    """The program's ``ModelConfig`` for a configuration's dimensions."""
    from repro.configs.model_config import ModelConfig
    return ModelConfig(
        name=name, family="dense", num_layers=m["num_layers"],
        d_model=m["d_model"], num_heads=m["num_heads"],
        num_kv_heads=m["num_kv_heads"], d_ff=m["d_ff"],
        vocab_size=m["vocab_size"], head_dim=m["head_dim"],
        mlp_type="swiglu", dtype="bfloat16", kv_cache_dtype="bfloat16",
        rope_theta=float(m["rope_theta"]), norm_eps=m["norm_eps"],
        tie_embeddings=m["tie_embeddings"])


# ------------------------------------------------------------ reference
def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    import jax.numpy as jnp
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs            # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(m: dict, mm):
    """The float32 forward of one sequence: logits at positions ``rows``."""
    import jax
    import jax.numpy as jnp
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps, theta = m["norm_eps"], m["rope_theta"]
    group = H // KV

    def layer(x, lp):
        S = x.shape[0]
        pos = jnp.arange(S)
        h = _rms(x, lp["ln1"].astype(jnp.float32), eps)
        a = lp["attn"]
        q = _rope(mm("sd,dhk->shk", h, a["wq"]), pos, theta)
        k = _rope(mm("sd,dhk->shk", h, a["wk"]), pos, theta)
        v = mm("sd,dhk->shk", h, a["wv"])
        k = jnp.repeat(k, group, axis=1)                      # (S, H, hd)
        v = jnp.repeat(v, group, axis=1)
        s = mm("qhk,shk->hqs", q, k) / np.sqrt(hd)
        causal = jnp.tril(jnp.ones((S, S), bool))
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = mm("hqs,shk->qhk", p, v)
        x = x + mm("qhk,hkd->qd", o, a["wo"])
        h = _rms(x, lp["ln2"].astype(jnp.float32), eps)
        f = lp["mlp"]
        g = mm("sd,df->sf", h, f["w_gate"])
        u = mm("sd,df->sf", h, f["w_up"])
        return x + mm("sf,fd->sd", jax.nn.silu(g) * u, f["w_down"]), None

    def fwd(params, tokens, rows):
        x = params["embed"]["table"][tokens].astype(jnp.float32)
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = _rms(x[rows], params["final_norm"].astype(jnp.float32), eps)
        w = (params["embed"]["table"].T if m["tie_embeddings"]
             else params["head"]["w"])
        return mm("sd,dv->sv", x, w)

    return fwd


# --------------------------------------------------------------- counts
def layer_params(m: dict) -> int:
    """Weights of every layer's projections and MLP."""
    d, L, ff = m["d_model"], m["num_layers"], m["d_ff"]
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    return L * (d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff)


def matmul_params(m: dict) -> int:
    """Weights a generated token multiplies against: the layers plus the
    output head (the embedding is a row lookup)."""
    return layer_params(m) + m["d_model"] * m["vocab_size"]


def attn_flops(m: dict, ctx: int) -> int:
    """QK^T and PV of one token over ``ctx`` keys, every layer alike."""
    return 4 * m["num_layers"] * m["num_heads"] * m["head_dim"] * ctx


def token_flops(m: dict, context: int) -> float:
    """FLOPs of one token at a position that attends ``context`` keys
    (itself included): 2 per weight, plus QK^T and PV over the context."""
    return 2.0 * matmul_params(m) + attn_flops(m, context)


def prefill_chunk_flops(m: dict, offset: int, n: int) -> float:
    """Tokens ``offset .. offset + n - 1`` of one prompt.  The output
    head is not counted: a prompt token's logits are not needed, and the
    one that samples the first token is a rounding error."""
    # sum over positions p of (p + 1) attended keys
    keys = n * offset + n * (n + 1) // 2
    return 2.0 * layer_params(m) * n + attn_flops(m, keys)


def kv_bytes_per_token(m: dict, dtype_bytes: int = 2) -> int:
    """K and V of one position over all layers, at the real head_dim."""
    return 2 * m["num_layers"] * m["num_kv_heads"] * m["head_dim"] * dtype_bytes


def kv_read_bytes(m: dict, ctx: int) -> int:
    """K and V bytes a token reads at context ``ctx``: every layer reads
    every cached position."""
    return kv_bytes_per_token(m) * ctx


def weight_bytes(m: dict, dtype_bytes: int = 2) -> int:
    """Weights one decode step must read: every layer and the head
    (norm weights are float32; the embedding rows read are negligible)."""
    d, L = m["d_model"], m["num_layers"]
    return matmul_params(m) * dtype_bytes + (2 * L + 1) * d * 4


def step_weight_bytes(m: dict, rows) -> int:
    """Weight bytes a decode step of ``rows`` rows reads: all of them,
    whatever the rows."""
    return weight_bytes(m)
