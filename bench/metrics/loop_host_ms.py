"""Engine loop: mean host ms of a pass of the serve loop (an
``engine.iteration`` span that starts in the traced span) less the
``runtime.wait`` spans inside it — the host time for which the
synchronous loop leaves the chip without a step (``bench/spans.py``)."""
from bench import spans


def read(obs):
    return spans.host_ms(obs.trace, "engine.iteration")
