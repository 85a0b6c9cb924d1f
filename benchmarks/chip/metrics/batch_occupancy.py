"""Mean share of decode slots live per decode step in the window (engine
counters ``occupancy_sum / decode_steps``)."""


def read(rec):
    c = rec["counters"]
    if not c.get("decode_steps"):
        return None
    return 100.0 * c["occupancy_sum"] / c["decode_steps"]
