"""Scheduler: 95th percentile of the queue wait, from a request's
scheduled arrival to the start of its first admission (host clock),
over the requests due in the window that were admitted."""
from bench.observed import p95


def read(obs):
    v = p95(r.t_admit - r.t_due for r in obs.records
            if r.t_admit is not None)
    return None if v is None else v * 1e3
