"""Median time to first token over every request scheduled in the
window, from its scheduled arrival to the client's first sight of a
token; a request with none by the window's end counts with its wait."""
import numpy as np


def read(rec):
    xs = rec["client"]["ttft_s"]
    return float(np.percentile(xs, 50)) * 1e3 if xs else None
