"""Runtime dispatch: mean host ms of a runtime call (a ``runtime.call``
span that starts in the traced span) less its ``runtime.wait``: the
policy exchange, the variant's resolution and the report after it
(``bench/spans.py``)."""
from bench import spans


def read(obs):
    return spans.host_ms(obs.trace, "runtime.call")
