#!/usr/bin/env python3
"""Chip smoke: serve llama3.2-3b at its published widths on a TPU.

    python3 chip_smoke.py             # one chip: the serve main path
    python3 chip_smoke.py --chips 4   # the mesh-sharded engine on four
                                      # chips against one device

Builds the published ``llama3.2-3b`` config (28 layers, bf16, random
weights from ``--seed``), drives requests through
:class:`repro.serve.ServeEngine` with an ``EngineConfig`` built the way
``repro.launch.serve`` builds one, and fails unless every check holds.
Throughput and compile times printed here come from one smoke run, not a
benchmark.  The last line of standard output is the JSON verdict
``{"ok": true, "device": {...}}``; it is printed only when every check
passed on a TPU.  Without a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import batch_config, init_serve_params  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serve import (SamplingParams, ServeEngine, add_cli_args,  # noqa
                         config_from_args)

__all__ = ["smoke", "smoke_sharded", "main"]

#: Largest relative L2 error ||engine - reference|| / ||reference|| of the
#: fp32 logits at one decoded position.  Both sides run bf16 weights and
#: activations; bf16 keeps 8 mantissa bits (unit roundoff 2^-9, one
#: rounding ~0.2-0.4% relative).  The engine's paged split-K decode and the
#: reference's flash-attention forward round at different points (chunked
#: prefill, bf16 probabilities before PV, page-partial sums) in each of
#: 28 layers, so their independent rounding errors add up to a few
#: percent of the logit vector; 5% leaves room for that and still fails on
#: a wrong position, mask or cache page, which decorrelate the logits
#: entirely (relative error near 1.4).  fp32 configs use 1e-4.
BF16_LOGIT_RTOL = 0.05
FP32_LOGIT_RTOL = 1e-4


def _log(msg: str) -> None:
    print(msg, flush=True)


def engine_config(*, slots: int, max_seq: int, prefill_chunk: int,
                  mesh_shards: int = 1):
    """The ``EngineConfig`` the serve CLI builds from these flags (its
    defaults: paged KV, prefix cache on, chain speculation with
    ``spec_k`` 4)."""
    ap = argparse.ArgumentParser()
    add_cli_args(ap)
    args = ap.parse_args(["--slots", str(slots), "--max-seq", str(max_seq),
                          "--prefill-chunk", str(prefill_chunk),
                          "--mesh-shards", str(mesh_shards)])
    return config_from_args(args)


def make_requests(vocab: int, n: int, lens, gen: int, seed: int,
                  shared_prefix: int = 0, n_shared: int = 0,
                  n_sampled: int = 1):
    """``n`` seeded requests ``(prompt, max_new, sampling)``: prompt
    lengths spread evenly over ``lens`` (shuffled), the first ``n_shared``
    prompts starting with one common ``shared_prefix``-token span, and
    the last ``n_sampled`` requests temperature-sampled (the rest
    greedy)."""
    rng = np.random.default_rng(seed)
    lengths = np.linspace(lens[0], lens[1], n).astype(int)
    rng.shuffle(lengths)
    prefix = rng.integers(0, vocab, (shared_prefix,)).tolist()
    reqs = []
    for i, length in enumerate(lengths):
        body = rng.integers(0, vocab, (int(length),)).tolist()
        prompt = (prefix + body[shared_prefix:]) if i < n_shared else body
        sp = (SamplingParams(temperature=0.8, top_p=0.95, seed=seed + i)
              if i >= n - n_sampled else None)
        reqs.append((prompt, gen, sp))
    return reqs


class _LaneLog:
    """Which request each lane of a decode dispatch served.

    The engine hands the scheduler one ``{slot: emitted tokens}`` map per
    decode step, built in the dispatch's lane order, right after the
    dispatch and before any retirement.  This wraps that call to record
    ``(request, tokens generated before the step, tokens emitted)`` per
    lane, which lines each row of the engine's logit trace up with a
    sequence position."""

    def __init__(self, sched):
        self.steps = []
        inner = sched.on_decode_tokens

        def record(tokens):
            lanes = [sched.active[s] for s in tokens]
            self.steps.append([(req, len(req.generated), len(t))
                               for req, t in zip(lanes, tokens.values())])
            return inner(tokens)

        sched.on_decode_tokens = record


def serve(eng, requests, keep_rows=lambda req: False):
    """Serve ``requests`` on ``eng`` to completion.

    Returns ``(reqs, rows, finite, seconds)``: the request handles in
    submission order, ``rows[rid][g]`` = fp32 logits that produced
    generated token ``g`` (for requests where ``keep_rows`` holds), whether
    every traced decode logit was finite, and the wall time.  The row
    mapping holds for plain and chain-speculative decode, where the
    emitted tokens of a lane come from its first trace rows."""
    log = _LaneLog(eng.scheduler)
    eng.trace_logits = True
    reqs = [eng.submit(p, g, sampling=sp) for p, g, sp in requests]
    keep = {r.rid for r in reqs if keep_rows(r)}
    rows = {rid: {} for rid in keep}
    finite = True
    t0 = time.perf_counter()
    while eng.scheduler.has_work:
        eng.step()
        for trace, lanes in zip(eng.logit_trace, log.steps):
            trace = trace.reshape(len(lanes), -1, trace.shape[-1])
            finite &= bool(np.isfinite(trace).all())
            for lane, (req, n_before, emitted) in enumerate(lanes):
                for j in range(emitted if req.rid in keep else 0):
                    rows[req.rid][n_before + j] = trace[lane, j]
        eng.logit_trace.clear()
        log.steps.clear()
    dt = time.perf_counter() - t0
    eng.trace_logits = False
    return reqs, rows, finite, dt


def _init_params(cfg, seed: int):
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_serve_params(cfg, seed))
    _log(f"params: {cfg.arch_id}, {cfg.n_layers} layers, d_model "
         f"{cfg.d_model}, {jnp.dtype(cfg.dtype).name}, "
         f"{sum(x.size for x in jax.tree.leaves(params)) / 1e9:.2f} B "
         f"initialized in {time.perf_counter() - t0:.1f} s")
    return params


def _all_retired(reqs, requests) -> bool:
    """Every request finished with its full generation."""
    return all(r.done and len(r.generated) == g
               for r, (_, g, _) in zip(reqs, requests))


def _on_device(tree, devices) -> bool:
    return all(leaf.devices() <= set(devices)
               for leaf in jax.tree.leaves(tree))


def _rel_errors(cfg, params, req, rows):
    """Per decoded position: relative L2 error of the engine's logits
    against ``lm.forward`` over prompt + generated tokens, and whether
    the reference's argmax equals the engine's greedy token."""
    seq = np.asarray(req.prompt + req.generated, np.int32)
    fwd = jax.jit(lambda p, t: lm.forward(p, {"tokens": t}, cfg)[0])
    ref = np.asarray(fwd(params, jnp.asarray(seq)[None])[0], np.float32)
    p = len(req.prompt)
    errs, agree = [], 0
    for g, row in sorted(rows.items()):
        r = ref[p + g - 1]
        errs.append(float(np.linalg.norm(row - r) / np.linalg.norm(r)))
        agree += int(np.argmax(r) == req.generated[g])
    return errs, agree


def smoke(cfg, *, slots=8, max_seq=2048, prefill_chunk=512,
          n_requests=16, prompt_lens=(100, 1500), gen=64, shared_prefix=512,
          n_shared=4, ref_len=1024, seed=0):
    """The one-chip smoke body: random weights for ``cfg`` from ``seed``,
    ``n_requests`` seeded requests through ``slots`` slots, then a second
    batch on the warm engine whose first request (greedy, prompt
    ``ref_len - gen`` tokens) is checked position by position against
    ``lm.forward`` over ``ref_len`` tokens.

    Returns ``{"checks": {name: bool}, "figures": {...}}``; a check that
    does not hold is False, never an exception, so the caller sees all
    of them."""
    checks, figs = {}, {}
    device = jax.devices()[0]
    params = _init_params(cfg, seed)
    ecfg = engine_config(slots=slots, max_seq=max_seq,
                         prefill_chunk=prefill_chunk)
    batch1 = make_requests(cfg.vocab, n_requests, prompt_lens, gen, seed,
                           shared_prefix, n_shared)
    ecfg = batch_config([p for p, _, _ in batch1], gen, config=ecfg,
                        max_seq=max_seq)
    eng = ServeEngine(cfg, params, config=ecfg)
    t0 = time.perf_counter()
    eng.warmup()
    figs["warmup_s"] = time.perf_counter() - t0
    figs["executables"] = len(eng._exe)
    _log(f"warm-up (compile + first runs): {figs['warmup_s']:.1f} s, "
         f"{figs['executables']} executables (one smoke run, not a "
         f"benchmark)")

    reqs, _, finite1, dt = serve(eng, batch1)
    tokens = sum(len(r.generated) for r in reqs)
    st = eng.stats_summary()
    figs.update(batch1_s=dt, batch1_tokens=tokens,
                prefix_hits=st["prefix_hits"],
                tokens_per_step=st["tokens_per_step"])
    _log(f"batch 1: {len(reqs)} requests, {tokens} tokens in {dt:.2f} s "
         f"({tokens / dt:.1f} tok/s; one smoke run, not a benchmark), "
         f"prefix hits {st['prefix_hits']:.0f}, "
         f"tokens/step {st['tokens_per_step']:.2f}")
    checks["batch1_all_retired"] = _all_retired(reqs, batch1)
    checks["params_and_state_on_device"] = (
        _on_device(eng.params, [device]) and _on_device(eng.state, [device]))

    # the split-K page combine must take the Pallas moa_reduce kernel on
    # the TPU (a tpu_custom_call in each decode executable) and the
    # in-register radix-4 tree elsewhere
    want_kernel = kops.on_tpu()
    for key in ("decode", "spec"):
        if key in eng._exe:
            n = eng._exe[key].as_text().count("tpu_custom_call")
            figs[f"{key}_tpu_custom_calls"] = n
            checks[f"{key}_combine_kernel"] = (n > 0) == want_kernel

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    before = set(eng._exe)
    ref_prompt = np.random.default_rng(seed + 1).integers(
        0, cfg.vocab, (ref_len - gen,)).tolist()
    batch2 = [(ref_prompt, gen, None)] + make_requests(
        cfg.vocab, 3, prompt_lens, gen, seed + 2, n_sampled=1)
    reqs2, rows, finite2, _ = serve(
        eng, batch2, keep_rows=lambda r: r.prompt == ref_prompt)
    figs["batch2_new_executables"] = len(set(eng._exe) - before)
    figs["batch2_backend_compiles"] = len(compiles)
    checks["batch2_compiles_nothing"] = (set(eng._exe) == before
                                         and not compiles)
    checks["batch2_all_retired"] = _all_retired(reqs2, batch2)
    checks["decode_logits_finite"] = finite1 and finite2

    ref = reqs2[0]
    errs, agree = _rel_errors(cfg, params, ref, rows[ref.rid])
    rtol = BF16_LOGIT_RTOL if cfg.dtype == jnp.bfloat16 else FP32_LOGIT_RTOL
    figs.update(ref_positions=len(errs), ref_max_rel_err=max(errs),
                ref_mean_rel_err=float(np.mean(errs)), ref_top1_agree=agree,
                ref_rtol=rtol)
    _log(f"reference: {len(errs)} decoded positions vs lm.forward over "
         f"{len(ref.prompt) + len(ref.generated)} tokens: relative logit "
         f"error max {max(errs):.5f} mean {np.mean(errs):.5f} "
         f"(limit {rtol}), argmax agrees at {agree}/{len(errs)}")
    checks["ref_positions_complete"] = len(errs) == gen - 1
    checks["ref_logits_agree"] = max(errs) <= rtol
    return {"checks": checks, "figures": figs}


def smoke_sharded(cfg, *, shards=4, slots=8, max_seq=2048,
                  prefill_chunk=256, n_requests=8, prompt_lens=(256, 768),
                  gen=32, seed=0):
    """The four-chip body: the same seeded requests through one engine
    with ``mesh_shards=shards`` over the first ``shards`` devices and one
    single-device engine on device 0, compared token for token and logit
    for logit.  The weights are replicated once over the mesh and the
    single-device engine serves device 0's replica, so no device holds
    two copies; the engines run one after the other, so device 0 never
    holds two engines' state.  Prompt lengths are multiples of
    ``prefill_chunk`` and no warm-up runs, so each engine compiles only
    the buckets and dispatches these requests use.  Returns
    ``{"checks", "figures"}``."""
    checks, figs = {}, {}
    dev0 = jax.devices()[0]
    rep = NamedSharding(Mesh(np.array(jax.devices()[:shards]), ("slots",)),
                        PartitionSpec())
    params_rep = jax.device_put(_init_params(cfg, seed), rep)
    params_0 = jax.tree.map(
        lambda a: next(s.data for s in a.addressable_shards
                       if s.device == dev0), params_rep)
    requests = make_requests(cfg.vocab, n_requests, prompt_lens, gen, seed,
                             n_sampled=2)
    requests = [(p[:len(p) // prefill_chunk * prefill_chunk], g, sp)
                for p, g, sp in requests]
    out = {}
    for shards_i in (1, shards):
        ecfg = batch_config([p for p, _, _ in requests], gen,
                            config=engine_config(
                                slots=slots, max_seq=max_seq,
                                prefill_chunk=prefill_chunk,
                                mesh_shards=shards_i),
                            max_seq=max_seq)
        eng = ServeEngine(cfg, params_rep if shards_i > 1 else params_0,
                          config=ecfg)
        if shards_i > 1:
            devs = list(eng.mesh_plan.mesh.devices.flat)
            figs["mesh_devices"] = [str(d) for d in devs]
            checks["mesh_spans_distinct_devices"] = len(set(devs)) == shards
            checks["sharded_state_on_mesh"] = all(
                len(leaf.devices()) == shards
                for leaf in jax.tree.leaves(eng.state))
        reqs, rows, finite, dt = serve(eng, requests,
                                       keep_rows=lambda r: True)
        figs[f"serve_s_{shards_i}"] = dt
        _log(f"mesh_shards={shards_i}: {len(reqs)} requests in {dt:.2f} s "
             f"(includes compiles; one smoke run, not a benchmark)")
        checks[f"all_retired_{shards_i}"] = _all_retired(reqs, requests)
        checks[f"logits_finite_{shards_i}"] = finite
        out[shards_i] = ([r.generated for r in reqs],
                         [rows[r.rid] for r in reqs])
        del eng, reqs, rows
        gc.collect()    # the engine and its scheduler hold each other
    (tok1, rows1), (tokn, rowsn) = out[1], out[shards]
    greedy = [sp is None for _, _, sp in requests]
    figs["greedy_tokens_equal"] = sum(
        a == b for a, b, gr in zip(tok1, tokn, greedy) if gr)
    figs["sampled_tokens_equal"] = sum(
        a == b for a, b, gr in zip(tok1, tokn, greedy) if not gr)
    diffs, n_rows, n_exact = [], 0, 0
    for r1, rn in zip(rows1, rowsn):
        for g in sorted(set(r1) & set(rn)):
            d = float(np.max(np.abs(r1[g] - rn[g])))
            diffs.append(d)
            n_rows += 1
            n_exact += int(d == 0.0)
    figs.update(logit_rows_compared=n_rows, logit_rows_bit_exact=n_exact,
                logit_max_abs_diff=max(diffs) if diffs else None)
    _log(f"sharded vs single: greedy tokens equal in "
         f"{figs['greedy_tokens_equal']}/{sum(greedy)} requests, sampled in "
         f"{figs['sampled_tokens_equal']}/{len(greedy) - sum(greedy)}; "
         f"logit rows bit-exact {n_exact}/{n_rows}, max |diff| "
         f"{figs['logit_max_abs_diff']}")
    checks["greedy_tokens_bit_exact"] = (
        figs["greedy_tokens_equal"] == sum(greedy))
    checks["sampled_tokens_bit_exact"] = (
        figs["sampled_tokens_equal"] == len(greedy) - sum(greedy))
    checks["logits_bit_exact"] = n_rows > 0 and n_exact == n_rows
    return {"checks": checks, "figures": figs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the serve main path on one chip; 4: the "
                         "mesh-sharded engine against one device")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform}); "
              f"this script runs only on the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    enable_compile_cache()
    _log(f"device: {devices[0].device_kind} x{len(devices)}, "
         f"jax {jax.__version__}")
    cfg = get_config("llama3.2-3b")
    if args.chips == 1:
        result = smoke(cfg, seed=args.seed)
    else:
        result = smoke_sharded(cfg, shards=args.chips, seed=args.seed)
    for name, val in result["figures"].items():
        _log(f"figure {name}: {val}")
    failed = [name for name, ok in result["checks"].items() if not ok]
    for name, ok in result["checks"].items():
        _log(f"check {name}: {'pass' if ok else 'FAIL'}")
    if failed:
        print(f"chip_smoke: {len(failed)} check(s) failed: {failed}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
