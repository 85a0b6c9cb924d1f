"""Arithmetic on the engine's own phase counters, shared by the readers
of a quantity split by cell kind (``metrics/decode_host_ms.online.py``
and ``.offline.py``).  A program without these counters gives None."""


def decode_host_ms(rec):
    """Mean host time around each decode step's timed window, in ms: the
    engine's ``decode_prepare_s`` (page allocation, lanes, page table,
    device puts) plus ``decode_commit_s`` (trie extends, token
    bookkeeping, row release) over its ``decode_steps``."""
    c = rec["counters"]
    if not c.get("decode_steps") or "decode_prepare_s" not in c:
        return None
    return 1e3 * (c["decode_prepare_s"] + c["decode_commit_s"]) \
        / c["decode_steps"]
