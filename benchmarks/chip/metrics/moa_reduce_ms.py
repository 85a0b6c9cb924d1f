"""Device time of the split-K page combine kernel (``moa_reduce``) per
decode step, in ms: its trace events inside ``jit_decode`` runs over the
number of those runs in the traced span."""

#: the kernel this metric reads, and the executable it is counted in
KERNEL = ("moa_reduce", "jit_decode")


def read(rec):
    tr = rec["trace"]
    k = tr and tr["kernels"].get(KERNEL[0])
    if not k or not k["module_runs"] or not k["in_module_s"]:
        return None
    return 1e3 * k["in_module_s"] / k["module_runs"]
