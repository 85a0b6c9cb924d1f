"""Share of admitted context tokens served from the prefix cache in the
window: ``prefix_reused_tokens / (prefill_tokens + prefix_reused_tokens)``
(engine counters)."""


def read(rec):
    c = rec["counters"]
    total = c.get("prefill_tokens", 0) + c.get("prefix_reused_tokens", 0)
    if not total:
        return None
    return 100.0 * c["prefix_reused_tokens"] / total
