"""Serving driver: chunked-prefill, continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
        --reduced --requests 8 --slots 4 --prompt-len 16 --gen 32 \
        --temperature 0.8 --top-p 0.95 --slo-ms 2000

Requests flow through :class:`repro.serve.ServeEngine`: prompts are
ingested by shape-bucketed chunked prefill (one jitted dispatch per prompt
block, shared prompt prefixes reused from resident slot pages), decode is
continuously batched — short and long requests share every decode step at
per-slot positions, finished slots are refilled mid-flight — and tokens are
sampled in-graph per slot (``--temperature 0`` = greedy).  Decode steps
are speculative by default (``--spec-k`` prompt-lookup drafts verified in
one K+1-wide dispatch, bit-exact vs sequential decode; ``--no-spec``
disables).  ``--spec-mode tree`` drafts a token *tree* per slot (n-gram
fan-out or ``--spec-drafter heads`` medusa-style draft heads) verified in
one ancestor-masked dispatch; ``--spec-mode auto`` lets a per-slot
accept-rate model pick chain vs tree shape every step.
``--kv-dtype int8``/``int4`` stores KV pages as per-row
quantized codes dequantized inside the decode kernel (paged engines only).
``--per-token`` instead runs :func:`generate`, the legacy
one-dispatch-per-token loop kept as the measurement baseline.  See
``docs/serving.md`` for the full request lifecycle and knob reference.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.models.common import init_params
from repro.models.registry import get_api
from repro.serve import (EngineConfig, SamplingParams, ServeEngine,
                         add_cli_args, config_from_args, state_zeros)

__all__ = ["main", "generate", "serve_batch", "batch_config",
           "init_serve_params"]


def generate(cfg, params, prompts: np.ndarray, gen: int,
             greedy: bool = True, seed: int = 0):
    """Legacy per-token serve loop (the measurement baseline).

    prompts: (B, P) int32. Returns (B, P+gen) generated ids + stats.
    One ``decode_step`` dispatch per token for every phase — prefill
    included — which is exactly the dispatch-bound shape the engine
    replaces.  Kept for baseline benchmarks and equivalence tests.
    """
    api = get_api(cfg)
    b, p = prompts.shape
    max_seq = p + gen
    # decode caches are declared zero-init: build zeros straight from the
    # specs instead of drawing random parameters only to zero them
    state = state_zeros(api.decode_state_specs(cfg, b, max_seq))
    dstep = jax.jit(lambda pr, s, batch: api.decode_step(pr, s, batch, cfg))
    toks = jnp.asarray(prompts, jnp.int32)
    # warm up OUTSIDE the timed region: the first call compiles; replaying
    # it on a discarded state keeps compile time out of prefill_s/decode_s
    dstep(params, state, {"tokens": toks[:, :1],
                          "index": jnp.asarray(0, jnp.int32)}
          )[0].block_until_ready()
    out = [toks]
    key = jax.random.key(seed)
    t_prefill = t_decode = 0.0
    cur = None
    for i in range(max_seq - 1):
        tok_i = (toks[:, i:i + 1] if i < p else cur)
        t0 = time.perf_counter()
        logits, state = dstep(params, state,
                              {"tokens": tok_i,
                               "index": jnp.asarray(i, jnp.int32)})
        logits.block_until_ready()
        dt = time.perf_counter() - t0
        if i < p - 1:
            t_prefill += dt
            continue
        t_decode += dt
        if greedy:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        else:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(sub, logits)[:, None].astype(
                jnp.int32)
        cur = nxt
        out.append(nxt)
    ids = jnp.concatenate(out, axis=1)
    return np.asarray(ids), {
        "prefill_s": t_prefill, "decode_s": t_decode,
        "prefill_tok_s": b * (p - 1) / max(t_prefill, 1e-9),
        "decode_tok_s": b * gen / max(t_decode, 1e-9)}


def batch_config(prompts, gens, *, config=None, slots=None, max_seq=None,
                 **knobs) -> EngineConfig:
    """Resolve the ``serve_batch`` knob surface into ONE
    :class:`~repro.serve.EngineConfig` (pure planning — no engine built,
    so tests can assert every knob lands without compiling a model).

    Args:
      prompts: list of 1-D int token lists (sizes the derived capacity).
      gens: per-request generation lengths (int or list).
      config: a ready-made :class:`~repro.serve.EngineConfig`; mutually
        exclusive with ``knobs``.
      slots: convenience alias for ``max_slots`` (the historical
        ``serve_batch`` spelling); overrides the config when given.
      max_seq: per-slot cache capacity.  ``0`` forces derivation from the
        longest request (padded to 16); ``None`` (default) derives too
        unless an explicit ``config`` was given (whose ``max_seq`` then
        stands); any other value is used as-is.
      knobs: any other :class:`~repro.serve.EngineConfig` field by name
        (``prefill_chunk``, ``page_size``, ``min_prefix``, ``spec_k``,
        ``spec_ngram``, ``trie_capacity``, ``kv_dtype``, ...).

    Returns:
      The fully-populated (but unresolved) config the engine will run.
    """
    if config is not None and knobs:
        raise TypeError(
            f"pass engine knobs via config= OR as keywords, not both "
            f"(got config= plus {sorted(knobs)})")
    ecfg = config if config is not None else EngineConfig(**knobs)
    if slots is not None:
        ecfg = ecfg.replace(max_slots=slots)
    if max_seq:
        ecfg = ecfg.replace(max_seq=max_seq)
    elif max_seq == 0 or config is None:
        if isinstance(gens, int):
            gens = [gens] * len(prompts)
        need = max(len(p) + g for p, g in zip(prompts, gens))
        ecfg = ecfg.replace(max_seq=max(16, -(-need // 16) * 16))
    return ecfg


def serve_batch(cfg, params, prompts, gens, *, config=None, slots=None,
                max_seq=None, sampling=None, slo_ms=None, **knobs):
    """Run a list of requests through the engine; returns (outputs, stats).

    Args:
      cfg: model config; params: model parameters.
      prompts: list of 1-D int token lists.
      gens: per-request generation lengths (int or list).
      config: a ready-made :class:`~repro.serve.EngineConfig` describing
        every engine knob; mutually exclusive with passing knobs as
        keywords.
      slots: decode batch width (alias for ``max_slots``).
      max_seq: per-slot cache capacity (``0`` or the default ``None`` =
        derived from the longest request, padded to 16; with an explicit
        ``config``, ``None`` keeps ``config.max_seq`` — see
        :func:`batch_config`).
      sampling: per-request :class:`SamplingParams`, one shared instance,
        or None for greedy decoding everywhere.
      slo_ms: per-request completion-latency SLO in ms (scalar or list;
        None = no SLO).
      knobs: any other :class:`~repro.serve.EngineConfig` field by name —
        ``prefill_chunk``, ``page_size``, ``prefix_cache``,
        ``min_prefix``, ``paged_kv``, ``pool_pages``, ``trie_capacity``,
        ``spec_k``, ``spec_ngram``, ``kv_dtype``.

    Returns:
      (outputs, stats): per-request generated-token lists in submission
      order, and the engine's :meth:`~repro.serve.ServeEngine.stats_summary`.
    """
    n = len(prompts)
    if isinstance(gens, int):
        gens = [gens] * n
    if sampling is None or isinstance(sampling, SamplingParams):
        sampling = [sampling] * n
    if slo_ms is None or isinstance(slo_ms, (int, float)):
        slo_ms = [slo_ms] * n
    ecfg = batch_config(prompts, gens, config=config, slots=slots,
                        max_seq=max_seq, **knobs)
    eng = ServeEngine(cfg, params, config=ecfg)
    # warm up BEFORE submitting: the SLO clock starts at submission, and
    # AOT compile / first-execution setup is engine bring-up, not request
    # latency (same reason the throughput timers exclude it)
    eng.warmup()
    reqs = [eng.submit(list(p), g, sampling=sp, slo_ms=sl)
            for p, g, sp, sl in zip(prompts, gens, sampling, slo_ms)]
    eng.run()
    return [r.generated for r in reqs], eng.stats_summary()


def init_serve_params(cfg, seed: int):
    """Random weights for serving ``cfg``, drawn from ``seed`` in one
    jitted program on the default device and stored in ``cfg.dtype``
    (llama3.2-3b: 7.2 GB in bf16 where fp32 would not leave room for the
    KV pool on a 16 GB chip)."""
    specs = get_api(cfg).param_specs(cfg)
    return jax.jit(lambda key: init_params(specs, key, dtype=cfg.dtype))(
        jax.random.key(seed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3.2-3b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="mean prompt length (lengths are staggered)")
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--per-token", action="store_true",
                    help="run the legacy per-token baseline loop instead")
    ap.add_argument("--burst-smoke", action="store_true",
                    help="replay a seeded bursty open-loop trace on the "
                         "virtual clock instead (exercises SLO pressure, "
                         "the degrade ladder and shedding end to end)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus truncation (1.0 = disabled)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-request completion-latency SLO in ms "
                         "(enables deadline-aware admission)")
    ap.add_argument("--seed", type=int, default=0)
    # every engine knob comes from the ONE shared EngineConfig binding
    # (--slots, --max-seq, --prefill-chunk, --page, --min-prefix,
    #  --no-prefix-cache, --no-paged-kv, --pool-pages, --trie-capacity,
    #  --spec-k/--no-spec, --spec-ngram, --kv-dtype)
    add_cli_args(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if cfg.encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only: no decode step")
    if args.reduced:
        cfg = cfg.reduced(dtype=jnp.float32)
    params = init_serve_params(cfg, args.seed)
    rng = np.random.default_rng(args.seed)

    if args.burst_smoke:
        # open-loop burst replay: arrivals do not wait for completions,
        # time is virtual (deterministic given --seed), tokens are real.
        # Degrade + SLOs are forced on — the whole point of the smoke is
        # driving the ladder through shed and back.
        from repro.tune.workloads import bursty_trace, replay_open_loop
        ecfg = config_from_args(args).replace(
            max_seq=(args.max_seq or 128), degrade=True)
        trace = bursty_trace(args.requests, rate=2.0, burst_rate=30.0,
                             mean_prompt=float(args.prompt_len),
                             mean_gen=float(args.gen),
                             max_prompt=ecfg.max_seq // 2,
                             max_gen=ecfg.max_seq // 4, vocab=cfg.vocab,
                             slo_ms=args.slo_ms or 900.0, seed=args.seed)
        eng = ServeEngine(cfg, params, config=ecfg)
        res = replay_open_loop(eng, trace)
        st = res["stats"]
        print(f"[burst] arch={cfg.arch_id} arrivals={len(trace)} "
              f"slots={ecfg.max_slots} virtual {res['elapsed_s']:.2f}s "
              f"in {res['steps']} engine steps")
        print(f"goodput {res['goodput_tok_s']:.1f} tok/s (virtual)  "
              f"SLO {res['slo_met']} met / {res['slo_missed']} missed  "
              f"shed {res['shed']}  degrade transitions "
              f"{st['degrade_transitions']:.0f} "
              f"(final level {st['degrade_level']:.0f})")
        shed = [r for r in res["finished"] if r.shed_reason is not None]
        if shed:
            print(f"first shed reason: {shed[0].shed_reason!r}")
        return 0

    if args.per_token:
        prompts = rng.integers(
            0, cfg.vocab, (args.max_slots, args.prompt_len)).astype(np.int32)
        ids, stats = generate(cfg, params, prompts, args.gen)
        print(f"[per-token] arch={cfg.arch_id} batch={args.max_slots} "
              f"prompt={args.prompt_len} gen={args.gen}")
        print(f"prefill {stats['prefill_s']:.2f}s "
              f"({stats['prefill_tok_s']:.1f} tok/s)  "
              f"decode {stats['decode_s']:.2f}s "
              f"({stats['decode_tok_s']:.1f} tok/s)")
        print(f"first request ids: {ids[0, :args.prompt_len]} -> "
              f"{ids[0, args.prompt_len:]}")
        return 0

    # staggered prompt lengths around --prompt-len: the continuous-batching
    # case (uniform lengths would never exercise refill)
    lens = [max(1, args.prompt_len + int(d))
            for d in rng.integers(-args.prompt_len // 2,
                                  args.prompt_len // 2 + 1, args.requests)]
    prompts = [rng.integers(0, cfg.vocab, (n,)).tolist() for n in lens]
    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.seed)
    outs, stats = serve_batch(cfg, params, prompts, args.gen,
                              config=config_from_args(args),
                              max_seq=args.max_seq,
                              sampling=sampling, slo_ms=args.slo_ms)
    print(f"[engine] arch={cfg.arch_id} requests={args.requests} "
          f"slots={args.max_slots} gen={args.gen} "
          f"prompt_lens={lens} sampling={sampling}")
    print(f"prefill {stats['prefill_s']:.2f}s "
          f"({stats['prefill_tok_s']:.1f} tok/s)  "
          f"decode {stats['decode_s']:.2f}s "
          f"({stats['decode_tok_s']:.1f} tok/s)  "
          f"occupancy {stats['mean_occupancy']:.0%}")
    print(f"prefix cache: {stats['prefix_hits']:.0f} hits / "
          f"{stats['prefix_misses']:.0f} misses "
          f"({stats['prefix_reused_tokens']:.0f} tokens reused, "
          f"{stats['pages_shared']:.0f} pages shared by reference, "
          f"{stats['prefix_bytes_copied']:.0f} bytes copied)")
    print(f"kv pages: dtype={stats['kv_dtype']} "
          f"{stats['kv_bytes_per_slot']:.0f} bytes/slot, "
          f"pool {stats['pool_bytes']:.0f} bytes")
    if stats["spec_k"]:
        print(f"speculative decode (k={stats['spec_k']:.0f}): "
              f"{stats['tokens_per_step']:.2f} tokens/step, "
              f"accept rate {stats['spec_accept_rate']:.0%}, "
              f"draft hit rate {stats['spec_draft_hit_rate']:.0%}, "
              f"decode step p50 {stats['decode_step_p50_s'] * 1e3:.2f}ms / "
              f"p99 {stats['decode_step_p99_s'] * 1e3:.2f}ms")
    if stats.get("spec_mode", "chain") != "chain":
        print(f"tree speculation (mode={stats['spec_mode']}, "
              f"nodes={stats['spec_tree_nodes']:.0f}, "
              f"branch={stats['spec_branch']:.0f}, "
              f"drafter={stats['spec_drafter']}): "
              f"{stats['spec_tree_steps']:.0f} tree steps, "
              f"accept p50 {stats['spec_accept_p50']:.2f} / "
              f"p99 {stats['spec_accept_p99']:.2f}, "
              f"shape picks chain={stats['spec_shape_chain']:.0f} "
              f"tree={stats['spec_shape_tree']:.0f}")
    if stats["mesh_shards"] > 1:
        print(f"mesh: {stats['mesh_shards']:.0f} shards, lane steps "
              f"{stats['shard_lane_steps']}, occupancy skew "
              f"{stats['shard_occupancy_skew']:.2f}")
    if args.slo_ms is not None:
        print(f"SLO {args.slo_ms:.0f}ms: {stats['slo_met']:.0f} met / "
              f"{stats['slo_missed']:.0f} missed  "
              f"(preemptions {stats['preemptions']:.0f})")
    print(f"first request: {prompts[0]} -> {outs[0]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
