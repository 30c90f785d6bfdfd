"""In-graph per-request token sampling for the serve decode step.

One vmapped kernel serves every request mix: it takes (B,) vectors of
temperature / top_k / top_p / seed alongside the (B, V) last-position
logits and the (B,) absolute token positions, so the jitted decode step
keeps ONE static compile signature no matter which sampling specs are
in flight (per ``core/binary.shape_key`` every leaf is keyed by
shape/dtype only — sampling params are *data*, not shapes, so no
per-request recompiles).

Determinism contract (the serve-migration analogue of Xar-Trek's
"migration must be transparent to the application"):

* the per-row PRNG key is ``fold_in(PRNGKey(seed), position)`` where
  ``position`` is the token's ABSOLUTE sequence position (prompt_len
  for the first generated token, prompt_len + k for the k-th).  The key
  depends only on (seed, position) — not on slot index, batch
  composition, wall clock, or how many times the request was preempted
  — so a seeded request replays identically across HOST/ACCEL builds,
  forced mid-stream migrations, and preempt/resume cycles.
* ``temperature == 0.0`` bypasses the sampled path entirely
  (``jnp.argmax`` over the raw logits), byte-identical to the greedy
  engines.
* the math is pure jnp traced identically into the HOST (XLA) and
  ACCEL (Pallas-attention) step builds — only attention differs between
  backends, never the sampling transform.

Filter order follows the common serving convention: temperature scale,
then top-k, then top-p (nucleus) over the surviving mass, then a
Gumbel-max draw (equivalent to a categorical sample, but needs no
normalisation and composes with the -inf masking).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

# keys of a serve batch dict that feed sampling, not the model forward
SAMPLING_KEYS = ("temperature", "top_k", "top_p", "seed")

NEG_INF = jnp.float32(-1e30)


def sampling_leaves(params, batch_size: int = 1) -> dict:
    """(B,)-vector leaves for one SamplingParams broadcast over a batch
    (the prefill path's B=1 case).  Kept here so every caller builds the
    exact same dtypes — a drifted dtype would silently fork the compile
    signature."""
    return {
        "temperature": np.full((batch_size,), params.temperature, np.float32),
        "top_k": np.full((batch_size,), params.top_k, np.int32),
        "top_p": np.full((batch_size,), params.top_p, np.float32),
        "seed": np.full((batch_size,), params.seed, np.int32),
    }


def _sample_row(logits, temperature, top_k, top_p, seed, pos):
    """One row: logits (V,) f32, scalars for the request's spec.

    Returns the sampled token id (int32).  Greedy (temperature 0) takes
    the argmax of the RAW logits — the exact pre-sampling behaviour.

    Both filters run in probability space off ONE descending sort
    (softmax is monotone, so the k-th largest prob is the k-th largest
    logit): ``pk`` is the top-k threshold, and top-p keeps the smallest
    sorted prefix covering ``top_p`` of the surviving mass (comparing
    against ``top_p * mass`` instead of renormalising — same selection,
    no second softmax or sort).  Every comparison is against an element
    of ``probs`` itself, so membership is exact, and the max-prob token
    always survives — the filters can never empty the support.
    """
    logits = logits.astype(jnp.float32)
    V = logits.shape[-1]
    greedy = jnp.argmax(logits).astype(jnp.int32)

    # deterministic per-(request, position) key — see module docstring
    key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)

    z = logits / jnp.maximum(temperature, jnp.float32(1e-6))
    probs = jax.nn.softmax(z)
    # descending; only the VALUES are used, so an unstable sort gives the
    # identical array (and halves the TPU compile time of every step)
    sp = jax.lax.sort(probs, is_stable=False)[::-1]

    # top-k: keep the k largest (k <= 0 disables; ties at the k-th value
    # are kept inclusively, which is deterministic)
    k_eff = jnp.clip(jnp.where(top_k > 0, top_k, V), 1, V)
    pk = sp[k_eff - 1]
    spk = jnp.where(sp >= pk, sp, 0.0)          # top-k survivors, sorted
    mass = jnp.sum(spk)

    # top-p (nucleus): keep while the mass BEFORE the token is < top_p
    # of the surviving mass.  top_p >= 1.0 disables explicitly — the
    # f32 cumsum can round (csum - spk) up to >= mass and would
    # otherwise drop valid tail tokens even at top_p == 1.0
    csum = jnp.cumsum(spk)
    keep_sorted = ((top_p >= 1.0) | ((csum - spk) < top_p * mass)) \
        & (spk > 0)
    thr = jnp.min(jnp.where(keep_sorted, spk, jnp.float32(jnp.inf)))
    z = jnp.where((probs >= thr) & (probs >= pk), z, NEG_INF)

    # Gumbel-max draw == categorical(softmax(z)), no normalisation needed
    g = jax.random.gumbel(key, (V,), jnp.float32)
    sampled = jnp.argmax(z + g).astype(jnp.int32)
    tok = jnp.where(temperature <= 0.0, greedy, sampled)
    # chosen-token logprob under the UNMODIFIED model distribution
    # (raw logits, before temperature/filters): well-defined for greedy
    # and sampled rows alike, and a pure function of (logits, tok) so it
    # is byte-identical across backends and preemption history.  Always
    # computed — the step signature must stay static whether or not the
    # request opted in (the engine decides what to surface).
    logp = jax.nn.log_softmax(logits)[tok]
    return tok, logp


def sample_tokens(logits, temperature, top_k, top_p, seed, pos):
    """Batched in-graph sampling.

    logits: (B, V); temperature/top_p: (B,) f32; top_k/seed/pos: (B,)
    i32 — ``pos`` is each row's absolute position of the token being
    sampled.  Returns ((B,) int32 token ids, (B,) f32 chosen-token
    logprobs under the raw model distribution).
    """
    return jax.vmap(_sample_row)(
        logits, temperature.astype(jnp.float32), top_k.astype(jnp.int32),
        top_p.astype(jnp.float32), seed.astype(jnp.int32),
        pos.astype(jnp.int32))
