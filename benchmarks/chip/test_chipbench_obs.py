"""CPU tests of the readers of the engine's own spans and counters: each
reads None from an engine without those counters (an older one) or with
nothing counted, and the arithmetic it states otherwise; and, on a small
cell run past the look for a chip, the engine's context counters equal
the work log the client keeps by wrapping the engine."""
import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, os.pardir, os.pardir, "src"))
sys.path.insert(0, HERE)

from chipbench import spec  # noqa: E402

READERS = ["first_token_hold_ms", "decode_host_ms.online",
           "decode_host_ms.offline"]
#: counters as a program without the phase counters reports them
OLD = {"decode_s": 1.5, "decode_steps": 10, "prefill_s": 0.3,
       "prefill_tokens": 700, "admissions": 3}
NEW = dict(OLD, admit_plan_s=0.01, admit_commit_s=0.02,
           decode_prepare_s=0.03, decode_commit_s=0.05,
           first_token_hold_s=0.6, first_tokens=3,
           prefill_context_tokens=9000, decode_context_tokens=4000)


def _rec(counters):
    return {"counters": counters}


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_without_the_counters(name):
    read = spec.metric_reader(name)
    assert read(_rec(OLD)) is None
    assert read(_rec({k: 0 for k in NEW})) is None


def test_readers_state_their_arithmetic():
    assert math.isclose(spec.metric_reader("first_token_hold_ms")(_rec(NEW)),
                        200.0)
    for name in ("decode_host_ms.online", "decode_host_ms.offline"):
        assert math.isclose(spec.metric_reader(name)(_rec(NEW)), 8.0)


def test_context_counters_equal_the_clients_work_log():
    """The engine's ``prefill_context_tokens`` / ``decode_context_tokens``
    equal the sums the client's wraps of ``_admit`` / ``_decode_once``
    log for the same work: set-up (shared prefix, pre-roll) and a short
    open-loop window of the small CPU cell."""
    from test_chipbench_cell import _cell
    from chipbench.cell import prepare

    setup = prepare(_cell(), 2 ** 31 + 23, 1.5)
    eng, client = setup.eng, setup.client
    client.run(1.5)
    stats = eng.stats
    assert stats["decode_steps"] > 0 and stats["admissions"] > 1
    work = {"prefill": 0, "decode": 0}
    for _, kind, _, ctx_sum, _ in client.work:
        work[kind] += ctx_sum
    assert stats["prefill_context_tokens"] == work["prefill"]
    assert stats["decode_context_tokens"] == work["decode"]
    client.detach()
