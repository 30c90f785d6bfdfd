"""Kernels: the paged decode attention's share of its HBM roofline, in
percent.

The least time to read, once, the K and V of every live pool position
that the ACCEL decode steps of the traced span read (the ``ctx`` of
their ``engine.decode`` spans, ``bench/flops.kv_read_bytes``) at
the HBM bandwidth — a span carries its rows' summed context, which is
exact where every layer reads every cached position — over the device seconds of the ops named
``paged_gqa_decode``.  The positions are counted whatever the kernel
walks, so the share cannot pass 100% and means the same for any
implementation of the kernel.  Nothing where HOST served every decode.
"""
from bench import flops, spans

KERNEL = "paged_gqa_decode"


def read(obs):
    decodes = spans.traced(obs.trace, "engine.decode")
    if not decodes:
        return None
    ctx = sum(s.args.get("ctx", 0)
              for s in spans.served_on(obs.trace, decodes, "accel"))
    dev_s = obs.trace.ops.get(KERNEL, 0.0)
    if ctx == 0 or dev_s <= 0:
        return None
    least = flops.kv_read_bytes(obs.dims, ctx) / obs.peaks["hbm_bytes_per_s"]
    return 100.0 * least / dev_s
