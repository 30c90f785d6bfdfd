"""``correct`` must come out false when the timed path is broken: the
harness runs end to end on the CPU at toy sizes (the look for a chip is
skipped) with a fault planted under it, and the control — the reference
at float8 operands in the program's place — comes out not correct by
the same rule and limit."""
import time

import numpy as np
import pytest

import _paths  # noqa: F401
from _tiny import CONTROL_LENGTHS, CONTROL_MODEL, OFFLINE, OPEN, PEAKS, tiny
from bench import run

CELLS = [OFFLINE, OPEN]


def _alter_tokens(suffix, at_offset=False):
    """Every call of the step ``suffix`` (with ``at_offset``, only a
    prefill that starts past position 0, as a prefix-cache hit does)
    returns other tokens than it sampled: a token altered where it is
    produced."""
    def on_built(served):
        for rt in served.runtimes:
            call = rt.call

            def altered(fn, *args, _call=call, **kw):
                out = _call(fn, *args, **kw)
                if not fn.endswith(suffix) or (
                        at_offset
                        and not int(np.asarray(args[-1]["offset"])[0])):
                    return out
                toks = (np.asarray(out[0]) + 1) % served.cfg.vocab_size
                return (toks,) + tuple(out[1:])

            rt.call = altered
    return on_built


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c[1])
@pytest.mark.parametrize("step", ["_decode", "_prefill_ctx",
                                  "_prefill_ctx@offset"])
def test_altered_tokens_are_not_correct(cell, step):
    fn, _, where = step.partition("@")
    out = run.run_cell(tiny(*cell), 2**34 + 1, 2.0, False,
                       time.perf_counter(),
                       on_built=_alter_tokens(fn, at_offset=bool(where)),
                       pk=PEAKS)
    chk = out["checks"]["max_logit_gap"]
    assert out["correct"] is False, chk
    assert chk["value"] > chk["limit"]


# the configuration of the benchmark's cells, under both traffic kinds
@pytest.mark.parametrize("cell", [OFFLINE, (OFFLINE[0], OPEN[1])],
                         ids=lambda c: c[1])
def test_control_reads_above_the_program(cell):
    out = run.run_cell(tiny(*cell, model=CONTROL_MODEL,
                            lengths=CONTROL_LENGTHS[cell[1]]),
                       2**34 + 2, 4.0, False, time.perf_counter(),
                       control=True, pk=PEAKS)
    chk = out["checks"]
    assert out["correct"] is True
    assert chk["control_max_logit_gap"]["value"] > 3 * chk["max_logit_gap"]["value"]
    # judged by the harness's own rule, the control is not correct
    assert out["control_correct"] is False, chk
    assert list(out)[-1] == "checks"
