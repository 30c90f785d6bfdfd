"""A second architecture for the harness's tests, added as files only: a
dense decoder with biased q/k/v projections, a tanh-GELU MLP and rotary
embeddings at a fixed base, whose configuration uses keys of its own
(``width``, ``depth``, ``kv_groups``, ...) and has no ``rope_theta``."""
from __future__ import annotations

import math

ROPE_BASE = 10_000.0
BIAS_STD = 0.1


def dims(c: dict) -> dict:
    return {"d_model": c["width"], "num_layers": c["depth"],
            "num_heads": c["heads"], "num_kv_heads": c["kv_groups"],
            "head_dim": c["width"] // c["heads"], "d_ff": c["ffn"],
            "vocab_size": c["vocab"], "norm_eps": float(c["eps"])}


def shapes(m: dict) -> dict:
    d, L, ff, V = m["d_model"], m["num_layers"], m["d_ff"], m["vocab_size"]
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    bf, f32 = "bfloat16", "float32"
    return {
        "embed": {"table": ((V, d), bf, 0.02)},
        "layers": {
            "attn": {"wq": ((L, d, H, hd), bf, 1 / math.sqrt(d)),
                     "wk": ((L, d, KV, hd), bf, 1 / math.sqrt(d)),
                     "wv": ((L, d, KV, hd), bf, 1 / math.sqrt(d)),
                     "wo": ((L, H, hd, d), bf, 1 / math.sqrt(H * hd)),
                     "bq": ((L, H, hd), bf, BIAS_STD),
                     "bk": ((L, KV, hd), bf, BIAS_STD),
                     "bv": ((L, KV, hd), bf, BIAS_STD)},
            "mlp": {"w_in": ((L, d, ff), bf, 1 / math.sqrt(d)),
                    "w_out": ((L, ff, d), bf, 1 / math.sqrt(ff))},
            "ln1": ((L, d), f32, "norm"),
            "ln2": ((L, d), f32, "norm"),
        },
        "final_norm": ((d,), f32, "norm"),
        "head": {"w": ((d, V), bf, 1 / math.sqrt(d))},
    }


def program_config(name: str, m: dict):
    from repro.configs.model_config import ModelConfig
    return ModelConfig(
        name=name, family="dense", num_layers=m["num_layers"],
        d_model=m["d_model"], num_heads=m["num_heads"],
        num_kv_heads=m["num_kv_heads"], d_ff=m["d_ff"],
        vocab_size=m["vocab_size"], head_dim=m["head_dim"], qkv_bias=True,
        mlp_type="gelu", dtype="bfloat16", kv_cache_dtype="bfloat16",
        rope_theta=ROPE_BASE, norm_eps=m["norm_eps"], tie_embeddings=False)


def forward(m: dict, mm):
    import jax
    import jax.numpy as jnp
    H, KV, hd, eps = (m["num_heads"], m["num_kv_heads"], m["head_dim"],
                      m["norm_eps"])

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rope(x, pos):
        freqs = ROPE_BASE ** -(jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        ang = pos[:, None].astype(jnp.float32) * freqs
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def layer(x, lp):
        S = x.shape[0]
        pos = jnp.arange(S)
        a = lp["attn"]
        f32 = lambda t: t.astype(jnp.float32)
        h = rms(x, f32(lp["ln1"]))
        q = rope(mm("sd,dhk->shk", h, a["wq"]) + f32(a["bq"]), pos)
        k = rope(mm("sd,dhk->shk", h, a["wk"]) + f32(a["bk"]), pos)
        v = mm("sd,dhk->shk", h, a["wv"]) + f32(a["bv"])
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = mm("qhk,shk->hqs", q, k) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((S, S), bool))
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        x = x + mm("qhk,hkd->qd", mm("hqs,shk->qhk", p, v), a["wo"])
        h = rms(x, f32(lp["ln2"]))
        g = jax.nn.gelu(mm("sd,df->sf", h, lp["mlp"]["w_in"]),
                        approximate=True)
        return x + mm("sf,fd->sd", g, lp["mlp"]["w_out"]), None

    def fwd(params, tokens, rows):
        x = params["embed"]["table"][tokens].astype(jnp.float32)
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = rms(x[rows], params["final_norm"].astype(jnp.float32))
        return mm("sd,dv->sv", x, params["head"]["w"])

    return fwd


# counts: biases and norms are left out, as a rounding error
def layer_params(m: dict) -> int:
    d, L, ff = m["d_model"], m["num_layers"], m["d_ff"]
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    return L * (2 * d * H * hd + 2 * d * KV * hd + 2 * d * ff)


def attn_flops(m: dict, ctx: int) -> int:
    return 4 * m["num_layers"] * m["num_heads"] * m["head_dim"] * ctx


def token_flops(m: dict, context: int) -> float:
    return 2.0 * (layer_params(m) + m["d_model"] * m["vocab_size"]) + \
        attn_flops(m, context)


def prefill_chunk_flops(m: dict, offset: int, n: int) -> float:
    return 2.0 * layer_params(m) * n + attn_flops(
        m, n * offset + n * (n + 1) // 2)


def kv_read_bytes(m: dict, ctx: int) -> int:
    return 2 * m["num_layers"] * m["num_kv_heads"] * m["head_dim"] * 2 * ctx


def step_weight_bytes(m: dict, rows) -> int:
    return 2 * (layer_params(m) + m["d_model"] * m["vocab_size"])
