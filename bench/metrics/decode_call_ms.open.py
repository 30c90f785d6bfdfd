"""Runtime dispatch: mean host-clock ms of a decode call (the runtime's
``call_log``, both builds) inside the window."""


def read(obs):
    ms = [c["ms"] for c in obs.calls("_decode")]
    return sum(ms) / len(ms) if ms else None
