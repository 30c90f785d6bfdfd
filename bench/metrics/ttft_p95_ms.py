"""95th percentile of time to first token over every request due in the
window, from its scheduled arrival to its first ``on_token``.  A request
that never produced a token is charged the whole wait it was given."""
from bench.observed import p95


def read(obs):
    vals = []
    for r in obs.records:
        if r.times:
            vals.append(r.times[0] - r.t_due)
        else:
            vals.append(obs.failed_ttft_s)
    v = p95(vals)
    return None if v is None else v * 1e3
