#!/usr/bin/env python3
"""Readings that set a cell's fixed numbers; not a benchmark run.

    python3 benchmarks/chip/calibrate.py knee --workload glm4-9b.chat \
        --rates 1.5,2,2.5,3 --seconds 30
    python3 benchmarks/chip/calibrate.py control --workload glm4-9b.chat \
        --seeds 11,12,13 --seconds 30

``knee``: the cell's traffic at each offered rate (steady Poisson arrivals
of the mix's lengths), one fresh engine per rate in one process, each set
up as a run of the cell is (``chipbench.cell.prepare``); prints
per rate the requests due and finished, the queue left at the end and the
latency tails.  The knee is the highest rate whose queue does not grow
over the window.

``control``: whole runs of the cell on several seeds in one process, each
with the program's and the fp8 control's widest logit gap (the two
readings a ``max_logit_gap`` limit is set between) and the verdict of
each: the program's ``correct`` and the control's ``control_correct``,
which has to come out false.

Both print one JSON line per reading and refuse to run without a TPU.
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
# the TPU library would otherwise log under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(HERE, os.pardir, os.pardir, "src"))
sys.path.insert(0, HERE)


def _emit(rec):
    print(json.dumps(rec), flush=True)


def knee(cell, rates, seconds, seed):
    import numpy as np

    from chipbench.cell import prepare

    params = None
    for rate in rates:
        mix = dict(cell["traffic"],
                   arrivals={"kind": "poisson", "rate_per_s": rate})
        setup = prepare(dict(cell, traffic=mix), seed, seconds, params=params)
        params, eng, client = setup.params, setup.eng, setup.client
        del setup
        q0 = len(eng.scheduler.pending)
        client.run(seconds / 2)
        q_mid = len(eng.scheduler.pending)
        client.run(seconds)
        end = client.now()
        s = client.summary(end)
        pct = lambda xs, q: float(np.percentile(xs, q)) if xs else None
        _emit({
            "workload": cell["name"], "rate": rate, "window_s": end,
            "due": s["attempted"], "finished": s["finished_in_window"],
            "queue_start": q0, "queue_mid": q_mid,
            "queue_end": len(eng.scheduler.pending),
            "active_end": len(eng.scheduler.active),
            "ttft_p90_ms": (pct(s["ttft_s"], 90) or 0) * 1e3,
            "itl_p95_ms": (pct(s["itl_s"], 95) or 0) * 1e3,
            "output_tok_s": s["output_tokens"] / end,
            "decode_step_ms": 1e3 * eng.stats["decode_s"]
            / max(1, eng.stats["decode_steps"]),
            "occupancy": eng.stats["occupancy_sum"]
            / max(1, eng.stats["decode_steps"])})
        client.detach()
        del eng, client
        gc.collect()


def control(cell, seeds, seconds):
    from chipbench.cell import run_cell

    for seed in seeds:
        t = time.perf_counter()
        out = run_cell(cell, seed, seconds, False, t_start=t,
                       log=lambda m: print(m, flush=True), control=True)
        cal = out["calibration"]
        _emit({
            "workload": cell["name"], "seed": seed,
            "correct": out["correct"],
            "control_correct": cal["control_correct"],
            "program_max_gap": cal["max_logit_gap"],
            "control_max_gap": cal["control_max_gap"],
            "limit": out["checks"]["max_logit_gap"]["limit"],
            "control_top1_differs": cal["control_top1_differs"],
            "served_tokens": cal["served_tokens"],
            "per_request_max_gap": cal["per_request_max_gap"],
            "reference_s": cal["reference_s"],
            "metrics": out["metrics"]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("knee", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="1")
    args = ap.parse_args(argv)
    from chipbench import spec

    cell = spec.resolve_cell(spec.load_benchmark(), args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: no TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.mode == "knee":
        knee(cell, [float(r) for r in args.rates.split(",")], args.seconds,
             seeds[0])
    else:
        control(cell, seeds, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
