"""Step programs: mean host-clock ms of a prefill-at-offset call (the
runtime's ``call_log``, every chunk bucket, both builds) in the window."""


def read(obs):
    ms = [c["ms"] for c in obs.calls("_prefill_ctx")]
    return sum(ms) / len(ms) if ms else None
