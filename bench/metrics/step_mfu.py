"""Whole step: model FLOPs of the traced span over the chip's bf16 peak
for the span's length, in percent.  FLOPs are those of the prefill
tokens computed (from each chunk's offset and width) and of the decode
tokens emitted in the span, attention over their actual contexts
(``bench/flops.py``)."""
from bench import flops


def read(obs):
    span = obs.traced_span()
    if obs.trace is None or span is None:
        return None
    lo, hi = span
    m = obs.dims
    fl = sum(flops.prefill_chunk_flops(m, off, n)
             for t, _, off, n in obs.ctx_log if lo <= t < hi)
    for r in obs.records:
        P = len(r.prompt)
        for i, t in enumerate(r.times):
            if i and lo <= t < hi:
                fl += flops.token_flops(m, P + i)
    if fl == 0:
        return None
    return 100.0 * fl / ((hi - lo) * obs.peaks["bf16_flops"] * obs.chips)
