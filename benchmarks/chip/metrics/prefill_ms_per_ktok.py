"""Engine prefill time per thousand prefilled tokens in the window (the
engine's ``prefill_s`` timer, which ends in a device sync, over its
``prefill_tokens`` counter)."""


def read(rec):
    c = rec["counters"]
    if not c.get("prefill_tokens"):
        return None
    return 1e6 * c["prefill_s"] / c["prefill_tokens"]
