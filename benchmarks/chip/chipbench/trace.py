"""From a profiler trace to device busy time, kernel time and idle gaps.

:func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps what the metrics need, as plain lists: the device planes' op and
module events, and the benchmark's own host spans (``bench.*``,
:mod:`chipbench.client`).  :func:`reduce` turns that into numbers; it is
pure, so a small recorded trace checks it (``fixtures/``).

* busy: the union of the device's op intervals inside the traced window
  (``bench.traced``); idle is the rest of the window;
* modules: device time per jitted executable (``jit_decode``, ...);
* kernel time: for each kernel a metric file names (``KERNEL`` in
  ``metrics/<name>.py``: the kernel's name and the executable it runs in),
  device time of the ops whose HLO instruction name holds the kernel's
  name, counted inside that executable;
* op names are the instruction names (``fusion.8``); an op event's name
  in the trace is its whole HLO text.  Loops (``while``) hold the ops of
  their bodies, so they count towards busy time but not in the op list;
* idle by span: each idle interval is laid against the host span it fell
  in, the innermost (admission, decode dispatch) before the outer loop.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.traced"
#: host spans, innermost first: an idle interval goes to the first that
#: covers it, and to "other" (the client's own bookkeeping) when none does
SPAN_ORDER = ("bench.admit", "bench.decode", "bench.submit", "bench.wait",
              "bench.step")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")
#: ops that hold other ops (their time is their bodies')
_CONTAINERS = ("while", "conditional", "call")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def extract(path: str, chips: int) -> Dict[str, Any]:
    """The events a reduction needs, from one ``.xplane.pb``.

    Returns ``{"devices": {id: {"ops": [[name, start_ns, dur_ns], ...],
    "modules": [...]}}, "host": [[name, start_ns, dur_ns], ...]}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, Dict[str, List]] = {}
    host: List[List] = []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m and int(m.group(1)) < chips:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    name = op_name(ev.name) if key == "ops" else ev.name
                    dev[key].append([name, ev.start_ns, ev.duration_ns])
        elif not m:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
    return {"devices": {str(k): v for k, v in sorted(devices.items())},
            "host": host}


def op_name(text: str) -> str:
    """``%fusion.8 = f32[...] fusion(...)`` -> ``fusion.8``."""
    return text.split(" = ", 1)[0].lstrip("%")


def clip(ev: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    """The events that overlap the first ``seconds`` of the traced span,
    with the span cut to that length: a small recorded trace that reduces
    like the whole."""
    win = [(s, d) for n, s, d in ev["host"] if n == WINDOW_SPAN]
    if not win:
        return ev
    lo, hi = win[0][0], win[0][0] + seconds * 1e9
    keep = lambda evs: [e for e in evs if e[1] < hi and e[1] + e[2] > lo]
    host = [e for e in keep(ev["host"]) if e[0] != WINDOW_SPAN]
    return {"devices": {k: {kk: keep(vv) for kk, vv in v.items()}
                        for k, v in ev["devices"].items()},
            "host": [[WINDOW_SPAN, lo, hi - lo]] + host}


# -------------------------------------------------------------- reduction
def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv
            if min(b, hi) > max(a, lo)]


def _complement(merged, lo, hi):
    gaps, t = [], lo
    for a, b in merged:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _intersect(a_iv, b_iv):
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a_iv) and j < len(b_iv):
        lo = max(a_iv[i][0], b_iv[j][0])
        hi = min(a_iv[i][1], b_iv[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a_iv[i][1] < b_iv[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(a_iv, b_iv):
    """``a_iv`` minus ``b_iv`` (sorted, disjoint)."""
    out, j = [], 0
    for a, b in a_iv:
        t = a
        while j < len(b_iv) and b_iv[j][1] <= t:
            j += 1
        k = j
        while k < len(b_iv) and b_iv[k][0] < b:
            if b_iv[k][0] > t:
                out.append((t, b_iv[k][0]))
            t = max(t, b_iv[k][1])
            k += 1
        if b > t:
            out.append((t, b))
    return out


def _attribute(gaps, spans: Dict[str, List[Tuple[float, float]]]
               ) -> Dict[str, float]:
    """Seconds of ``gaps`` under each span name (innermost first)."""
    out: Dict[str, float] = {}
    rest = gaps
    for name in SPAN_ORDER:
        cover = _merge(spans.get(name, []))
        got = sum(b - a for a, b in _intersect(rest, cover))
        if got:
            out[name[len("bench."):]] = got
        rest = _subtract(rest, cover)
    left = sum(b - a for a, b in rest)
    if left:
        out["other"] = left
    return out


def module_name(name: str) -> str:
    return _MODULE_SUFFIX.sub("", name)


def reduce(ev: Dict[str, Any], kernels: Optional[Dict[str, str]] = None
           ) -> Optional[Dict[str, Any]]:
    """Numbers from :func:`extract`'s events (times in seconds).

    ``kernels`` maps a kernel's name to the executable it is counted in
    (``{"moa_reduce": "jit_decode"}``); each gets ``"kernels"[name]``:
    its device time in the window (``s``), the part inside runs of its
    executable (``in_module_s``) and the number of those runs
    (``module_runs``).  Returns None when the trace holds no window span
    or no device op."""
    kernels = dict(kernels or {})
    win = [(s, d) for n, s, d in ev["host"] if n == WINDOW_SPAN]
    if not win:
        return None
    base = win[0][0]                 # times relative to the window start

    def sec(t_ns):
        return (t_ns - base) * 1e-9

    ns = 1e-9
    lo, hi = 0.0, win[0][1] * ns
    spans: Dict[str, List[Tuple[float, float]]] = {}
    for n, s, d in ev["host"]:
        spans.setdefault(n, []).append((sec(s), sec(s) + d * ns))
    per_dev = []
    for dev in ev["devices"].values():
        ops = _clip([(sec(s), sec(s) + d * ns) for _, s, d in dev["ops"]],
                    lo, hi)
        if not ops:
            continue
        busy_iv = _merge(ops)
        busy = sum(b - a for a, b in busy_iv)
        mods: Dict[str, List[float]] = {}
        runs: Dict[str, List[Tuple[float, float]]] = {
            m: [] for m in kernels.values()}
        for n, s, d in dev["modules"]:
            a = sec(s)
            b = a + d * ns
            if lo <= a < hi:
                name = module_name(n)
                rec = mods.setdefault(name, [0, 0.0])
                rec[0] += 1
                rec[1] += b - a
                if name in runs:
                    runs[name].append((a, b))
        runs = {m: _merge(iv) for m, iv in runs.items()}
        op_time: Dict[str, float] = {}
        kernel_of: Dict[str, Optional[str]] = {}
        found = {k: {"s": 0.0, "in_module_s": 0.0,
                     "module_runs": mods.get(m, [0])[0]}
                 for k, m in kernels.items()}
        for n, s, d in dev["ops"]:
            a = sec(s)
            if not lo <= a < hi:
                continue
            if not n.startswith(_CONTAINERS):
                op_time[n] = op_time.get(n, 0.0) + d * ns
            if n not in kernel_of:
                kernel_of[n] = next((k for k in kernels if k in n), None)
            k = kernel_of[n]
            if k is not None:
                found[k]["s"] += d * ns
                if any(ra <= a < rb for ra, rb in
                       _bracket(runs[kernels[k]], a)):
                    found[k]["in_module_s"] += d * ns
        per_dev.append({
            "busy_s": busy,
            "modules": mods,
            "ops_top": sorted(op_time.items(), key=lambda kv: -kv[1])[:10],
            "kernels": found,
            "idle_by_span": _attribute(_complement(busy_iv, lo, hi), spans),
        })
    if not per_dev:
        return None
    n = len(per_dev)
    first = per_dev[0]
    return {
        "window_s": hi - lo,
        "busy_s": sum(d["busy_s"] for d in per_dev) / n,
        "modules": first["modules"],
        "ops_top": first["ops_top"],
        "kernels": first["kernels"],
        "idle_by_span": first["idle_by_span"],
    }


def _bracket(runs, a):
    """The run (if any) that could hold time ``a`` (runs are sorted)."""
    i = bisect.bisect_right(runs, (a, float("inf"))) - 1
    return [runs[i]] if i >= 0 else []
