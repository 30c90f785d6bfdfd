"""Plain reference forward of a Llama-architecture decoder.

Written from the published architecture (RMSNorm, rotary embeddings on
the two halves of each head, grouped-query attention, SwiGLU MLP) in
float32 at the highest matmul precision, with no cache, kernel or
batching.  It imports nothing of the program under test and reads only
the weights that ``bench/weights.py`` made.

``precision="fp8"`` is the control: the same forward with every matmul
operand (weights and activations) rounded to float8 e4m3 — the
precision step below the bfloat16 the configurations serve in.  A
program that computed that coarsely must fail the comparison.
"""
from __future__ import annotations

import functools

import numpy as np

PRECISIONS = ("float32", "fp8")


def _round(x, precision):
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    if precision == "fp8":
        x = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


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


@functools.lru_cache(maxsize=None)
def _forward_fn(dims: tuple, precision: str, n_out: int):
    """Jitted forward for one (shape set, precision, output rows): the
    tokens are padded to a fixed length, so one compile serves every
    request of a configuration."""
    import jax
    import jax.numpy as jnp
    m = dict(dims)
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps, theta = m["norm_eps"], m["rope_theta"]
    group = H // KV
    r = functools.partial(_round, precision=precision)

    def mm(spec, a, b):
        return jnp.einsum(spec, r(a), r(b))

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

    def run(params, tokens, rows):
        with jax.default_matmul_precision("highest"):
            return fwd(params, tokens, rows)

    return jax.jit(run)


# rows read per call: at least every served token of the longest reply
OUT_ROWS = 1024
# sequences pad to a power of two from this up, so few programs compile
MIN_PAD = 512


def padded_length(n: int) -> int:
    p = MIN_PAD
    while p < n:
        p *= 2
    return p


def logits(params, dims: dict, tokens, rows, *, precision: str = "float32"):
    """float32 logits (OUT_ROWS, vocab), on the device, whose first
    ``len(rows)`` rows are the positions ``rows`` of the sequence
    ``tokens`` (the rest repeat position 0).  Positions past
    ``len(tokens)`` are padding, which causal attention keeps out of
    every row that is read."""
    import jax.numpy as jnp
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    if rows.max() >= len(tokens) or len(rows) > OUT_ROWS:
        raise ValueError(f"rows must lie inside tokens, at most {OUT_ROWS}")
    pad_to = padded_length(len(tokens))
    n_out = OUT_ROWS
    toks = np.zeros((pad_to,), np.int32)
    toks[:len(tokens)] = tokens
    rws = np.zeros((n_out,), np.int32)
    rws[:len(rows)] = rows
    fn = _forward_fn(tuple(sorted(dims.items())), precision, n_out)
    return fn(params, jnp.asarray(toks), jnp.asarray(rws))
