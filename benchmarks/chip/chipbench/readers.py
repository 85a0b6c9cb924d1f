"""Arithmetic shared by the readers of a quantity split by cell kind
(``metrics/<name>.online.py`` and ``.offline.py`` read the same thing in
cells that report different end-to-end metrics)."""


def decode_step_ms(rec):
    """Mean decode step in the window: the engine's ``decode_s`` timer
    (dispatch to tokens on the host) over its ``decode_steps``."""
    c = rec["counters"]
    if not c.get("decode_steps"):
        return None
    return 1e3 * c["decode_s"] / c["decode_steps"]


def mfu(rec):
    """Model FLOPs of the work done in the traced span (prefill tokens
    computed and decode tokens of live lanes; no idle lane, padding or
    reused prefix) over the span's seconds times the chip's bf16 peak."""
    tr, traced = rec["trace"], rec["traced"]
    if tr is None or not traced or not traced["flops"]:
        return None
    return 100.0 * traced["flops"] / (tr["window_s"]
                                      * rec["peaks"]["bf16_flops_per_s"])


def device_idle_frac(rec):
    """Share of the traced span in which no operation ran on the device
    (1 - the union of device-op intervals over the span)."""
    tr = rec["trace"]
    if tr is None or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
