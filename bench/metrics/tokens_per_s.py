"""Output tokens emitted inside the window, over the window's seconds."""


def read(obs):
    t0, t1 = obs.window.t0, obs.window.t1
    n = sum(sum(1 for t in r.times if t0 <= t < t1) for r in obs.records)
    return n / obs.seconds
