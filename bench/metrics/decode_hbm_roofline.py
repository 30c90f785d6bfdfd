"""Step programs: the decode step's share of its roofline, in percent.

The least time the decode steps of the traced span could take — the
larger of their bytes over the HBM bandwidth and their FLOPs over the
bf16 peak — over the device time of the decode program in the trace.
Bytes: the weights once per step, the K and V of every live position
once, and the new positions written (``bench/flops.py``), from the
context lengths of the tokens decoded in the span.  Both builds compile
the same step function, so this reads the same work whichever serves.
"""
from bench import flops

PROGRAM = r"^jit_decode_fn"


def read(obs):
    span = obs.traced_span()
    if obs.trace is None or span is None:
        return None
    dev_s, steps = obs.trace.program_seconds(PROGRAM)
    if steps == 0 or dev_s <= 0:
        return None
    steps /= obs.trace.devices
    lo, hi = span
    m = obs.dims
    rows = kv = 0
    fl = 0.0
    for r in obs.records:
        P = len(r.prompt)
        for i, t in enumerate(r.times):
            if i == 0 or not lo <= t < hi:
                continue                  # token 0 comes from the prefill
            ctx = P + i - 1               # cached positions the step read
            rows += 1
            kv += flops.kv_read_bytes(m, ctx)
            fl += flops.token_flops(m, ctx + 1)
    if rows == 0:
        return None
    # a step's weights at the span's mean rows a step; each row writes
    # one new position
    byts = steps * flops.step_weight_bytes(m, rows / steps) + (
        kv + rows * flops.kv_read_bytes(m, 1))
    pk = obs.peaks
    least = max(byts / pk["hbm_bytes_per_s"], fl / pk["bf16_flops"])
    return 100.0 * least / dev_s
