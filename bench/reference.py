"""Plain reference forward of the served model.

The architecture's own forward (``bench/arch/<model_type>.py``, e.g. a
Llama decoder: RMSNorm, rotary embeddings, grouped-query attention,
SwiGLU), run here over one sequence in float32 at the highest matmul
precision, with no cache, kernel or batching.  It imports nothing of the
program under test and reads only the weights that ``bench/weights.py``
made.

``precision="fp8"`` is the control: the same forward with every matmul
operand (weights and activations) rounded to float8 e4m3 — the
precision step below the bfloat16 the configurations serve in.  A
program that computed that coarsely must fail the comparison.
"""
from __future__ import annotations

import functools

import numpy as np

from bench import arch

PRECISIONS = ("float32", "fp8")


def _round(x, precision):
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    if precision == "fp8":
        x = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


@functools.lru_cache(maxsize=None)
def _forward_fn(dims: tuple, precision: str, n_out: int):
    """Jitted forward for one (shape set, precision, output rows): the
    tokens are padded to a fixed length, so one compile serves every
    request of a configuration."""
    import jax
    import jax.numpy as jnp
    m = dict(dims)
    r = functools.partial(_round, precision=precision)

    def mm(spec, a, b):
        return jnp.einsum(spec, r(a), r(b))

    fwd = arch.load(m["model_type"]).forward(m, mm)

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
