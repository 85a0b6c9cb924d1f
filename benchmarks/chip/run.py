#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, on the chip it starts on.

    python3 benchmarks/chip/run.py --workload glm4-9b.chat --seed 7 \
        --seconds 51 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix; this loads, warms up, serves the mix open-loop on the real
clock for ``--seconds``, checks a sample of what it served against the
plain reference, and prints as its last line one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
``breakdown`` (traced runs) and, last, ``checks``: each number compared
with its limit, which also end standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result; it never falls back to the CPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# the TPU library would otherwise log under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(HERE, os.pardir, os.pardir, "src"))
sys.path.insert(0, HERE)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dump", default=None,
                    help="write the first 0.5 s of the traced span's "
                         "events here (a recorded trace for the tests)")
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench import spec

    cell = spec.resolve_cell(spec.load_benchmark(), args.workload)
    import repro  # noqa: F401  (the system under test must be present)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU (JAX sees {devices[0].platform}); the "
              f"benchmark runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"run.py: {args.workload} needs {cell['chips']} chips, JAX "
              f"sees {len(devices)}", file=sys.stderr)
        return 2
    spec.peaks_for(devices[0].device_kind)
    from repro.launch.compile_cache import enable_compile_cache
    from chipbench.cell import run_cell

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START, log=log, trace_dump=args.trace_dump)
    log(f"run: {time.perf_counter() - T_START:.1f} s from process start")
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
