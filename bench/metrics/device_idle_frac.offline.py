"""Device: 1 - (union of device op time) / traced span, mean over chips."""


def read(obs):
    t = obs.trace
    if t is None or t.window_s <= 0:
        return None
    return 1.0 - t.busy_mean_s / t.window_s
