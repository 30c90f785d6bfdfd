"""Scheduler: mean live rows per decode step in the window
(``decode_row_util / decode_steps x max_slots``, summed over replicas)."""


def read(obs):
    steps = sum(s["decode_steps"] for s in obs.window.stats)
    util = sum(s["decode_row_util"] for s in obs.window.stats)
    return util / steps * obs.max_slots if steps else None
