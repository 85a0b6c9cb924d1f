"""95th percentile of the gaps between consecutive output tokens, pooled
over every request, for gaps ending inside the window."""
import numpy as np


def read(rec):
    xs = rec["client"]["itl_s"]
    return float(np.percentile(xs, 95)) * 1e3 if xs else None
