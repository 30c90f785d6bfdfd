"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample
of the greedy requests the window finished — drawn from the seed, with
the longest among them and at least one from every replica — is run
through the plain reference (``bench/reference.py``, float32, highest
precision) over each prompt followed by its served tokens.  At every
served position the reference's best logit is compared with its logit
for the token the program served; the widest such gap over the sample,
``max_logit_gap``, is the number compared with the configuration's
limit.

The control (``control=True``) puts the reference in the program's
place at the precision step below the served bfloat16 (float8 e4m3
operands): at each position the token it ranks first is read under the
float32 reference in the same way.
"""
from __future__ import annotations

import functools

import numpy as np

from bench import reference
from bench.traffic import rng_for


def sample(records, seed: int, min_tokens: int, max_requests: int) -> list:
    """Finished greedy requests: the longest, then one per replica not
    yet covered, then more in seeded order until ``min_tokens`` served
    tokens or ``max_requests`` requests."""
    cands = [r for r in records if r.greedy and r.complete and not r.aborted]
    if not cands:
        return []
    cands.sort(key=lambda r: r.req_id)
    longest = max(cands, key=lambda r: (len(r.prompt) + r.max_new, -r.req_id))
    order = [cands[i] for i in rng_for(seed, 9).permutation(len(cands))]
    chosen, seen = [longest], {longest.replica}
    for r in order:
        if r.replica not in seen and len(chosen) < max_requests:
            chosen.append(r)
            seen.add(r.replica)
    n = sum(r.max_new for r in chosen)
    for r in order:
        if n >= min_tokens or len(chosen) >= max_requests:
            break
        if r not in chosen:
            chosen.append(r)
            n += r.max_new
    return chosen


@functools.lru_cache(maxsize=None)
def _gap_fn():
    """Widest gap between the reference's best logit and its logit for
    the chosen token, over the first ``n`` rows (fixed shapes: one
    compile)."""
    import jax
    import jax.numpy as jnp

    def gap(z, chosen, n):
        rows = jnp.arange(z.shape[0])
        g = jnp.max(z, axis=-1) - z[rows, chosen]
        return jnp.max(jnp.where(rows < n, g, -jnp.inf))

    return jax.jit(gap)


def _gaps(params, dims, prompt, served, control):
    import jax.numpy as jnp
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(seq))
    n = len(rows)
    chosen = np.zeros((reference.OUT_ROWS,), np.int32)
    chosen[:n] = served
    z = reference.logits(params, dims, seq, rows)
    out = {"program": float(_gap_fn()(z, jnp.asarray(chosen), n))}
    if control:
        c = reference.logits(params, dims, seq, rows, precision="fp8")
        pick = jnp.argmax(c, axis=-1).astype(jnp.int32)
        out["control"] = float(_gap_fn()(z, pick, n))
    return out


def compare(params, dims: dict, recs: list, control: bool = False) -> dict:
    """{"max_logit_gap": widest program gap, "tokens": positions compared,
    "requests": n, "replicas": covered} (+ "control_gap")."""
    worst, worst_ctrl, n = 0.0, 0.0, 0
    for r in recs:
        g = _gaps(params, dims, np.asarray(r.prompt, np.int32), r.tokens,
                  control)
        worst = max(worst, g["program"])
        worst_ctrl = max(worst_ctrl, g.get("control", 0.0))
        n += len(r.tokens)
    out = {"max_logit_gap": worst, "tokens": n, "requests": len(recs),
           "replicas": len({r.replica for r in recs})}
    if control:
        out["control_gap"] = worst_ctrl
    return out
