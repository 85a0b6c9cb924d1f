"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything specific to one configuration, one traffic mix or one metric
sits in a file of its own under the benchmark's directory and is found by
the name ``BENCHMARK.json`` gives it, so a cell is added by adding files
and entries, never by editing one that is there.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, Optional

#: the benchmark's own directory (``benchmarks/chip``)
BENCH_DIR = Path(__file__).resolve().parents[1]
#: the checkout's root, where ``BENCHMARK.json`` lies
ROOT = BENCH_DIR.parents[1]


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def load_config(name: str) -> Dict[str, Any]:
    return load_json(BENCH_DIR / "configs" / f"{name}.json")


def load_traffic(name: str) -> Dict[str, Any]:
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def load_peaks() -> Dict[str, Any]:
    return load_json(BENCH_DIR / "peaks.json")


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = load_peaks()["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return table[device_kind]


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(kind: str):
    """The plain reference of one layer kind, ``refs/<kind>.py``: its
    weights, its layer and its operation counts.  An unknown kind is an
    error."""
    path = BENCH_DIR / "refs" / f"{kind}.py"
    if not path.is_file():
        known = sorted(p.stem for p in path.parent.glob("*.py"))
        raise KeyError(f"no reference for layer kind {kind!r}; refs/ has "
                       f"{known}")
    return _load_module(path, f"chipbench_ref_{kind}")


def metric_module(name: str):
    """``metrics/<name>.py``: ``read(record)`` gives the metric's value,
    or None where the run holds nothing for it to read; a metric of a
    kernel also names it, ``KERNEL = (kernel, executable)``."""
    return _load_module(BENCH_DIR / "metrics" / f"{name}.py",
                        "chipbench_metric_" + name.replace(".", "_"))


def metric_reader(name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    return metric_module(name).read


def metric_kernels(metrics) -> Dict[str, str]:
    """The kernels that ``metrics`` (entries of ``BENCHMARK.json``) read
    from the trace: kernel name -> the executable it is counted in."""
    out = {}
    for m in metrics:
        k = getattr(metric_module(m["name"]), "KERNEL", None)
        if k is not None:
            out[k[0]] = k[1]
    return out


def resolve_cell(bench: Dict[str, Any], workload: str) -> Dict[str, Any]:
    """Everything one run of ``workload`` needs: the workload entry, its
    configuration and traffic files, and the metrics it reports with
    ``--trace 0`` (end to end) and ``--trace 1`` (per layer)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    cell = cells[workload]

    def applies(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "name": workload,
        "chips": int(cell["chips"]),
        "config": load_config(cell["config"]),
        "traffic": load_traffic(cell["traffic"]),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }
