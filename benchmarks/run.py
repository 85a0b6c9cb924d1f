"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only <name>]

Benchmarks that return a JSON-serializable dict get it persisted to
``results/BENCH_<name>.json`` so successive PRs accumulate a comparable
perf trajectory.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from benchmarks import (bench_adders, bench_autotune, bench_carry_tables,
                        bench_cla_vs_lut, bench_collectives, bench_lemma3,
                        bench_moa_kernels, bench_neuron, bench_serve,
                        bench_transition)
from repro.launch.compile_cache import enable_compile_cache

BENCHES = {
    "carry_tables": (bench_carry_tables, "Tables 1a/1b/1c + 2"),
    "transition": (bench_transition, "Table 3 / eqn 20"),
    "adders": (bench_adders, "Figs 12-15 adder sims"),
    "lemma3": (bench_lemma3, "Fig 9 / Lemma 3"),
    "cla_vs_lut": (bench_cla_vs_lut, "Figs 16-18 gate costs"),
    "moa_kernels": (bench_moa_kernels, "kernel layer"),
    "neuron": (bench_neuron, "§8 neurons"),
    "collectives": (bench_collectives, "§7 tree collectives"),
    "serve": (bench_serve, "chunked-prefill continuous-batching engine"),
    "autotune": (bench_autotune, "EngineConfig knob sweep + Pareto front"),
}


def _json_default(o):
    """numpy scalars/arrays serialize by value; anything else is rejected
    so garbage reprs never pollute the perf-trajectory files."""
    import numpy as np
    if isinstance(o, np.generic):
        return o.item()
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(f"{type(o).__name__} is not JSON-serializable")


def _persist(name: str, result, elapsed_s: float) -> None:
    """Write results/BENCH_<name>.json for dict-returning benchmarks.

    Persistence is best-effort: a read-only checkout or a bad result value
    must not turn a passing benchmark into a failure."""
    if not isinstance(result, dict):
        return
    path = os.path.join("results", f"BENCH_{name}.json")
    try:
        payload = json.dumps({"bench": name, "elapsed_s": round(elapsed_s, 3),
                              **result}, indent=1, default=_json_default)
        os.makedirs("results", exist_ok=True)
        with open(path, "w") as f:
            f.write(payload)
        print(f"[bench {name}] wrote {path}")
    except TypeError as e:
        print(f"[bench {name}] result not JSON-serializable ({e}); skipped")
    except OSError as e:
        print(f"[bench {name}] could not write {path} ({e}); skipped")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(BENCHES))
    args = ap.parse_args(argv)
    enable_compile_cache()
    names = [args.only] if args.only else list(BENCHES)
    failures = []
    for name in names:
        mod, desc = BENCHES[name]
        print(f"\n{'#' * 72}\n# bench: {name} — {desc}\n{'#' * 72}")
        t0 = time.monotonic()
        try:
            result = mod.run()
            _persist(name, result, time.monotonic() - t0)
            print(f"\n[bench {name}] OK in {time.monotonic() - t0:.1f}s")
        except Exception:
            failures.append(name)
            print(f"\n[bench {name}] FAILED:")
            traceback.print_exc()
    print(f"\n{'=' * 72}")
    if failures:
        print(f"{len(failures)} benchmark(s) failed: {failures}")
        return 1
    print(f"all {len(names)} benchmarks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
