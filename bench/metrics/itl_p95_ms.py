"""95th percentile over every gap between consecutive tokens of every
request due in the window (host-clock ``on_token`` timestamps)."""
import numpy as np

from bench.observed import p95


def read(obs):
    gaps = [np.diff(r.times) for r in obs.records if len(r.times) > 1]
    if not gaps:
        return None
    v = p95(np.concatenate(gaps))
    return None if v is None else v * 1e3
