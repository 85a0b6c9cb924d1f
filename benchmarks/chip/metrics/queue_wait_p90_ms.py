"""90th percentile, over requests scheduled in the window, of scheduled
arrival to the start of the request's admission (the benchmark's wrap of
the engine's ``_admit``); one not admitted by the window's end counts
with its wait."""
import numpy as np


def read(rec):
    xs = rec["client"]["queue_wait_s"]
    return float(np.percentile(xs, 90)) * 1e3 if xs else None
