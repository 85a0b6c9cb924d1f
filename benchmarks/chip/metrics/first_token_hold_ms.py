"""Mean time a first token waits inside the engine in the window, in ms:
from the readback of the prefill that sampled it to the end of the
``step()`` that admitted it, which runs one decode step after the
admissions (engine counters ``first_token_hold_s / first_tokens``).  A
program without these counters gives None."""


def read(rec):
    c = rec["counters"]
    if not c.get("first_tokens"):
        return None
    return 1e3 * c["first_token_hold_s"] / c["first_tokens"]
