"""One run of one cell: set-up, the measured window, the verdict.

Set-up (``setup_s``, from process start to the window's start) makes the
weights on the device from the seed, builds the engine, runs its own
``warmup()`` (every executable it will use, from the compile cache when a
run in this checkout has compiled them), serves the shared prefix where
the mix has one, and runs the pre-roll: the same traffic, from
``-preroll_s`` on, so the window opens on a batch in steady state.  The
window then runs ``seconds`` on the real clock; only requests scheduled
inside it count as attempted.  With ``trace`` the profiler records the
last ``TRACE_SECONDS`` of the window (the ``bench.traced`` span): the
per-layer metrics of the device are read over that span, those of the
client and the engine's counters over the whole window.  After the
window: the device's peak memory is read, the program is freed, and the
reference judges a sample of the finished requests.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import correct, spec
from chipbench import trace as T
from chipbench.client import Client
from chipbench.traffic import Traffic

#: seconds at the end of the window that a ``--trace 1`` run traces
TRACE_SECONDS = 8.0
#: where a traced run writes its profile (inside the checkout; removed
#: once read)
TRACE_DIR = spec.ROOT / ".bench_trace"


def model_config(config: Dict[str, Any]):
    """The program's model configuration: every ``ModelConfig`` field
    that the file's ``model`` names (``family`` among them), the dtype
    by its name."""
    from repro.configs.base import ModelConfig

    kw = dict(config["model"])
    kw["dtype"] = getattr(jnp, kw["dtype"])
    return ModelConfig(arch_id=config["name"], **kw)


@dataclasses.dataclass
class Setup:
    """What set-up hands to the window."""

    params: Any
    eng: Any
    traffic: Traffic
    client: Client
    phases: Dict[str, float]


def prepare(cell: Dict[str, Any], seed: int, seconds: float, *,
            params: Any = None, annotate: bool = False) -> Setup:
    """Set-up of one run of ``cell`` up to the window's start: weights
    from the seed (unless ``params`` are given), the engine and its
    ``warmup()``, the shared prefix, and the pre-roll of the traffic of
    a ``seconds`` window."""
    from repro.launch.serve import init_serve_params
    from repro.serve import EngineConfig, ServeEngine

    config, mix = cell["config"], cell["traffic"]
    cfg = model_config(config)
    phases = {}
    t = time.perf_counter()
    if params is None:
        params = jax.block_until_ready(init_serve_params(cfg, seed))
    phases["weights"] = time.perf_counter() - t
    t = time.perf_counter()
    eng = ServeEngine(cfg, params, config=EngineConfig(**mix["engine"]))
    eng.warmup()
    phases["warmup"] = time.perf_counter() - t
    phases["executables"] = len(eng._exe)
    preroll = float(mix["preroll_s"])
    traffic = Traffic(mix, seed, cfg.vocab, preroll, seconds)
    client = Client(eng, traffic, annotate=annotate)
    t = time.perf_counter()
    if traffic.shared_prefix:
        client.admit_prefix(traffic.prefix)
    client.start(preroll)
    client.run(0.0)
    phases["prefix_and_preroll"] = time.perf_counter() - t
    return Setup(params, eng, traffic, client, phases)


def _options():
    """Device and host activity, without the Python function tracer (its
    events would outnumber the device's and slow the host)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


def run_cell(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
             *, t_start: float, log: Callable[[str], None] = print,
             control: bool = False, trace_dump: Optional[str] = None
             ) -> Dict[str, Any]:
    """Run ``cell`` once; returns the result line's fields (and, with
    ``control``, the comparison under ``"calibration"``, with the
    control's own verdict as ``control_correct``)."""
    config, mix = cell["config"], cell["traffic"]
    setup = prepare(cell, seed, seconds, annotate=trace)
    eng, traffic, client, phases = (setup.eng, setup.traffic, setup.client,
                                    setup.phases)
    del setup

    compiles = []
    counting = [True]
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if counting[0] and name == "/jax/core/compile/backend_compile_duration"
        else None)
    stats0 = dict(eng.stats)
    setup_s = time.perf_counter() - t_start
    traced = None
    if trace:
        client.run(max(0.0, seconds - TRACE_SECONDS))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        t = time.perf_counter()
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=_options())
        phases["start_trace"] = time.perf_counter() - t
        t0, st0 = client.now(), dict(eng.stats)
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            client.run(seconds)
        traced = {"t": (t0, client.now()),
                  "counters": _delta(st0, dict(eng.stats))}
    else:
        client.run(seconds)
    end = client.now()
    counting[0] = False
    if trace:
        t = time.perf_counter()
        jax.profiler.stop_trace()
        phases["stop_trace"] = time.perf_counter() - t
    summary = client.summary(end)
    counters = _delta(stats0, dict(eng.stats))
    device = jax.devices()[0]
    mem = (device.memory_stats() or {}).get("peak_bytes_in_use")
    late = summary["submit_late_s"]
    log(f"setup: {json.dumps(phases)}")
    log(f"window: {end:.3f} s, {summary['attempted']} requests due, "
        f"{summary['finished_in_window']} of them finished, "
        f"{summary['output_tokens']} tokens; generator late p50 "
        f"{_percentile(late, 50)} s, max {max(late) if late else None} s; "
        f"backend compiles in window: {len(compiles)}")

    samples = correct.sample(client.finished(end), seed,
                             int(mix["check"]["sample_requests"]))
    prefix = traffic.prefix
    client.detach()
    del eng
    gc.collect()

    rec = {"client": summary, "setup_s": setup_s, "counters": counters,
           "model": config["model"],
           "peaks": None, "trace": None, "traced": None}
    breakdown = None
    if trace:
        peaks = spec.peaks_for(device.device_kind)
        path = T.find_xplane(str(TRACE_DIR))
        ev = T.extract(path, cell["chips"])
        if trace_dump:
            Path(trace_dump).parent.mkdir(parents=True, exist_ok=True)
            with open(trace_dump, "w") as f:
                json.dump(T.clip(ev, 0.5), f)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        red = T.reduce(ev, spec.metric_kernels(cell["per_layer"]))
        from chipbench.cost import work_flops
        t0, t1 = traced["t"]
        traced["flops"] = work_flops(
            config["model"], spec.load_reference(config["reference"]),
            [w for w in client.work if t0 <= w[0] < t1])
        rec.update(peaks=peaks, trace=red, traced=traced)
        if red is not None:
            breakdown = _breakdown(red)
    cmp = correct.compare(config, mix, seed, samples, prefix,
                          control=control)
    v = correct.verdict(config, mix, cmp)
    log(f"reference: {cmp['requests']} requests, {cmp['served_tokens']} "
        f"served tokens compared in {cmp['reference_s']:.1f} s; widest "
        f"gap per request {cmp['per_request_max_gap']}")

    metrics = {}
    for m in (cell["per_layer"] if trace else cell["end_to_end"]):
        val = spec.metric_reader(m["name"])(rec)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": mem}
    if trace and rec["trace"] is not None:
        dev["busy_s"] = rec["trace"]["busy_s"]
        dev["window_s"] = rec["trace"]["window_s"]
    out = {"correct": v["correct"], "attempted": summary["attempted"],
           "failed": summary["failed"], "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        cv = correct.control_verdict(config, mix, cmp)
        out["calibration"] = dict(cmp, control_correct=cv["correct"])
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in v["checks"].items()}
    return out


def _delta(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, float]:
    return {k: b[k] - a[k] for k in b
            if isinstance(b[k], (int, float)) and k in a}


def _breakdown(red: Dict[str, Any]) -> Dict[str, Any]:
    """The traced window's top device time (executables first, then
    single ops) and its idle time by what the host was doing."""
    mods = sorted(red["modules"].items(), key=lambda kv: -kv[1][1])
    ops = [[name, secs] for name, secs in red["ops_top"]]
    device_ops = ([[name, rec[1]] for name, rec in mods] + ops)[:10]
    idle = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": device_ops,
            "idle_gaps": [[name, secs] for name, secs in idle]}
