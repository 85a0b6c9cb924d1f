"""Chunked-prefill, continuous-batching serve engine.

The production serve-loop shape the seed repo was missing:

* **Chunked prefill** — one jitted dispatch ingests a whole prompt block
  (``prefill_chunk``), instead of P sequential ``decode_step`` dispatches.
  Chunks are shape-bucketed (powers of two up to ``prefill_chunk``) so the
  number of distinct compilations is O(log chunk), not O(prompt lengths).
* **Continuous batching** — a :class:`~repro.serve.scheduler.Scheduler`
  admits/evicts requests into a fixed-width decode batch; every decode step
  advances ALL live slots at their own per-slot positions (the vector-index
  decode path), and a slot freed by a finished request is refilled by the
  next admission while the rest keep decoding.
* **In-graph sampling** — each request carries
  :class:`~repro.serve.sampling.SamplingParams`; the jitted decode dispatch
  samples every slot at once from per-slot ``(B,)`` temperature / top-k /
  top-p / PRNG lanes (:func:`~repro.serve.sampling.sample_tokens`).
  ``temperature=0`` is the greedy fast path, bit-exact with argmax decode.
* **Prefix-cache reuse** — a host-side :class:`~repro.serve.cache.PrefixTrie`
  tracks the token prefix materialized in each slot's pages; a new request
  whose prompt extends a resident (or recently retired) prefix copies those
  pages and skips chunked prefill for the shared span.
* **SLO-aware admission** — the scheduler orders admissions earliest
  deadline first under an engine-fed cost model and can preempt a live
  request (which still meets its own SLO after re-queue) to rescue an
  at-risk pending one.
* **Paged slot state** — per-request KV/SSM state lives in slot pages of one
  shared batched tree (:mod:`repro.serve.cache`); admission resets exactly
  one slot, never the whole batch.
* **Paged allocation** (``paged_kv``, auto-on for positional state trees) —
  positional leaves live in a physical page pool with per-slot page-index
  vectors; a prefix-cache hit shares full pages *by reference* (refcount
  bump, zero bytes copied) and copy-on-writes at most the partial boundary
  page, so hit admission cost is O(1 page) instead of O(prefix).  Pages
  are allocated lazily as writes reach them; pool exhaustion defers
  admissions (never drops them) and reclaims the least-recently-used
  retired entries first.  Idle decode lanes aim their writes at the
  reserved scratch page, so retired-but-reusable pages can never be
  corrupted by the shared dispatch.
* **Speculative multi-token decode** (``spec_k > 0``) — the sequential
  one-token-per-dispatch decode loop replaced by the paper's wide parallel
  step: a model-free prompt-lookup drafter
  (:class:`~repro.serve.spec.PromptLookupDrafter`) proposes up to K
  candidate tokens per slot from its own history, ONE ``verify_chunk``
  dispatch scores all K+1 positions, and longest-matching-prefix
  acceptance (:func:`~repro.serve.spec.accept_tokens`) emits 1..K+1 tokens
  per slot per step — bit-exact vs sequential decode for greedy *and*
  stochastic lanes, because every emitted token is the sample the
  sequential engine would have drawn at that index.  Rejected draft
  positions are rolled back by rewinding per-slot lengths and releasing
  any page advanced past the accepted point (refcount-conserving).
  Auto-off for families whose state cannot be rewound position-wise
  (SSM/hybrid), like paged allocation.
* **Tree speculative decode** (``spec_mode="tree"``/``"auto"``) — the chain
  draft generalized to a branching token *tree*: per slot, a drafter
  (n-gram fan-out over the incremental per-slot
  :class:`~repro.serve.spec.SuffixCache`, or medusa-style trained draft
  heads) proposes a :class:`~repro.serve.spec.TreeDraft` of up to
  ``spec_tree_nodes`` nodes with ``spec_branch``-way hedges, and ONE
  ``verify_tree`` dispatch scores the whole flattened tree under an
  ancestor attention mask.  Acceptance walks the longest sampled-matching
  root-to-leaf path (:func:`~repro.serve.spec.accept_path`) — bit-exact vs
  sequential for greedy and stochastic lanes, because each row samples at
  its own depth's sequential index.  Drafted rows commit only to the
  scratch page; accepted tokens materialize as the *chain part* of the
  NEXT step's block, so rejection needs no page rollback at all.  In
  ``"auto"`` mode a per-slot accept-rate EWMA feeds the paper's Lemma-3
  closed-form expected-tokens model and the engine picks chain-K or
  tree-(a, d) per slot per step (decision trace in ``stats_summary``).
* **Shared reduction engine** — with ``page_size`` set, decode attention
  runs the paged split-K path: per-page partial accumulators combined by
  the same radix-4 :class:`~repro.dist.plan.ReductionPlan` tree that shapes
  the in-register, in-VMEM and cross-device reduction tiers.

All jitted entry points are compiled ahead-of-time from shape structs
(``jit(f).lower(...).compile()``), so **reported timings never include
compile time** — the engine times only executions of already-compiled
functions.

**Observability** — every phase of the serve loop is a
``jax.profiler.TraceAnnotation`` span (``serve.step``, ``serve.admit``
and its ``.plan`` / ``.prefill`` / ``.commit`` phases, ``serve.decode``
and its ``.prepare`` / ``.device`` / ``.commit`` phases), so a profiler
trace lays device idle time against what the host was doing; the host
phases also add their seconds to ``stats`` (``admit_plan_s``,
``decode_prepare_s``, ...).  Inside every executable the model call and
the sampling run under ``jax.named_scope("model")`` and ``("sample")``,
and each executable is named by its key (``jit_decode``,
``jit_prefill_b<bucket>``).  See ``docs/serving.md`` "Observability".
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import ParamSpec, shape_structs
from repro.models.registry import get_api
from repro.models import quant_kv
from repro.serve import cache
from repro.serve.config import EngineConfig, auto_page_size
from repro.serve.mesh import MeshPlan
from repro.serve.sampling import (GREEDY, SamplingParams, sample_tokens,
                                  sampling_lanes)
from repro.serve.scheduler import DegradeLadder, Request, Scheduler
from repro.serve.sessions import SessionStore
from repro.serve.spec import (DraftHeadDrafter, NGramTreeDrafter,
                              PromptLookupDrafter, TreeDraft, accept_path,
                              accept_tokens, expected_tokens_chain,
                              expected_tokens_tree, per_candidate_accept,
                              pick_shape)

__all__ = ["ServeEngine", "auto_page_size"]

#: EWMA weight for the scheduler cost model's newest timing sample.
_COST_EWMA = 0.5

#: EWMA weight for the per-slot accept-rate estimate the Lemma-3
#: reconfigurator consumes (slower than the timing EWMA: a single
#: rejected tree must not swing the topology decision).
_ACCEPT_EWMA = 0.3

#: Per-candidate accept rate assumed for a slot with no measurements yet
#: (fresh admission): optimistic enough that auto mode tries drafting.
_ACCEPT_PRIOR = 0.5

#: Bound on the reconfigurator decision trace kept for stats_summary.
_DECISION_TRACE = 64

#: Auto-mode exploration cadence: when a shape has lost this many
#: consecutive reconfigurator decisions on a slot, run it once anyway to
#: refresh its accept EWMA — a stale losing estimate can otherwise never
#: recover (the shape that never runs is never measured).
_EXPLORE_EVERY = 16

#: Sliding-window length for the per-event latency samples behind the
#: percentile summaries (a long-lived engine must not grow a float per
#: decode step forever; 4096 recent steps bound both the memory and the
#: cost of the np.percentile at stats_summary time).
_LATENCY_WINDOW = 4096


def _buckets(chunk: int, lo: int = 8) -> Tuple[int, ...]:
    """Power-of-two prefill shape buckets up to ``chunk`` (inclusive)."""
    out, b = [], lo
    while b < chunk:
        out.append(b)
        b *= 2
    out.append(chunk)
    return tuple(out)


class ServeEngine:
    """Continuous-batching engine over one model's decode state.

    Constructed from a model config + params and ONE
    :class:`~repro.serve.config.EngineConfig` describing every knob
    (``ServeEngine(cfg, params, config=EngineConfig(spec_k=4))``); for
    convenience the same knobs are accepted directly as keywords
    (``ServeEngine(cfg, params, spec_k=4)``) and collected into a config —
    passing both forms at once is an error.  All knob validation and
    auto-resolution (page size, family gating, quantization fallback,
    pool sizing) lives in :meth:`EngineConfig.validate` /
    :meth:`EngineConfig.resolve`, NOT here; the resolved config is kept
    as ``self.config``.  See ``docs/serving.md`` for the knob table and
    :class:`~repro.serve.config.EngineConfig` for per-knob semantics.

    Quantized engines additionally audit the page-sum accumulator width
    at build time with the paper's exact carry math
    (:func:`repro.models.quant_kv.assert_kv_accumulator`).
    """

    def __init__(self, cfg, params, *,
                 config: Optional[EngineConfig] = None, **knobs):
        if config is None:
            config = EngineConfig(**knobs)
        elif knobs:
            raise TypeError(
                f"pass engine knobs via config= OR as keywords, not both "
                f"(got config= plus {sorted(knobs)})")
        ecfg = config.resolve(cfg)
        self.config = ecfg
        api = get_api(cfg)
        max_slots, max_seq = ecfg.max_slots, ecfg.max_seq
        page_size = ecfg.page_size
        self.cfg = dataclasses.replace(cfg, decode_page_size=page_size)
        self.api = api
        self.params = params
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.prefill_chunk = ecfg.prefill_chunk
        self.page_size = page_size
        self.min_prefix = ecfg.min_prefix
        self.chunk_buckets = _buckets(ecfg.prefill_chunk)
        self.scheduler = Scheduler.from_config(ecfg)
        self.specs = api.decode_state_specs(self.cfg, max_slots, max_seq)
        self.spec_k = ecfg.spec_k
        self.drafter = (PromptLookupDrafter(ngram_max=ecfg.spec_ngram)
                        if ecfg.spec_k else None)
        # tree speculative decode (resolve() forced spec_mode back to
        # "chain" when the family has no verify_tree or spec_k is 0)
        self.spec_mode = ecfg.spec_mode
        self.spec_tree_nodes = ecfg.spec_tree_nodes
        self.spec_branch = ecfg.spec_branch
        self.spec_drafter = ecfg.spec_drafter
        self.tree_drafter = (NGramTreeDrafter(ngram_max=ecfg.spec_ngram)
                             if self.spec_mode != "chain" else None)
        # medusa-style heads need trained weights in the checkpoint; a
        # params tree without them falls back to the n-gram tree drafter
        self.head_drafter = None
        if self.spec_mode != "chain" and ecfg.spec_drafter == "heads" \
                and "draft_heads" in params:
            self.head_drafter = DraftHeadDrafter(
                n_heads=int(params["draft_heads"]["w1"].shape[0]))
        #: per-slot incremental suffix-lookup caches (chain AND tree
        #: drafting both consult them; fresh on every admission)
        self._suffix_caches: Dict[int, Any] = {}
        #: per-slot count of emitted-but-unmaterialized tokens (the chain
        #: part the next tree step commits; 1 after admission — chain
        #: decode's implicit invariant made explicit)
        self._spec_unwritten: Dict[int, int] = {}
        #: per-slot (H, A) draft-head candidates at the last accepted row
        self._head_preds: Dict[int, np.ndarray] = {}
        #: per-slot, per-shape accept-rate EWMAs (per drafted candidate)
        #: — the reconfigurator's inputs and the p50/p99 accept stats'
        #: population.  Keyed ``slot -> {"chain"|"tree": p}``: the two
        #: shapes may draft through different predictors (n-gram vs
        #: draft heads), so each is estimated from its own steps
        self._slot_accept: Dict[int, Dict[str, float]] = {}
        #: per-slot decisions since each shape last ran (auto-mode
        #: exploration clock, see ``_EXPLORE_EVERY``)
        self._shape_age: Dict[int, Dict[str, int]] = {}
        #: per-slot emitted-tokens-per-step EWMA (scheduler cost feed)
        self._slot_tps: Dict[int, float] = {}
        self.paged = bool(ecfg.paged_kv)
        self.shards = ecfg.mesh_shards
        kv_dtype = ecfg.kv_dtype
        self.kv_dtype = kv_dtype
        if self.paged:
            self.max_pages = max_seq // page_size
            pool_pages = ecfg.pool_pages
            # one scratch page per shard (mesh_shards=1: the classic +1)
            self.pool = cache.PagePool(pool_pages + self.shards,
                                       shards=self.shards)
            self.pspecs = cache.paged_state_specs(
                self.specs, page_size, pool_pages + self.shards)
            if kv_dtype != "fp32":
                # build-time audit: page_size int{bits} magnitudes must sum
                # exactly inside the int32 carrier (paper's carry math)
                quant_kv.assert_kv_accumulator(
                    page_size, 8 if kv_dtype == "int8" else 4)
                self.pspecs = cache.quant_state_specs(self.pspecs, kv_dtype)
            self.state = cache.state_zeros(self.pspecs)
            # per-slot page tables; 0 = the scratch page (unallocated)
            self.table = np.zeros((max_slots, self.max_pages), np.int32)
            self.page_bytes = cache.state_bytes(self.pspecs) // (
                pool_pages + self.shards)
        else:
            self.state = cache.state_zeros(self.specs)
        # ---- mesh plan: shard slots + the page pool across devices;
        # weights replicate, the pooled state splits its phys_page axis
        # into per-device blocks, and all placement happens ONCE here —
        # every dispatch's out_specs keep state/tokens/logits sharded, so
        # steady-state decode moves zero cross-device bytes
        self.mesh_plan = MeshPlan.build(ecfg) if self.shards > 1 else None
        if self.mesh_plan is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            plan = self.mesh_plan
            self._spec_lane = plan.lane_spec()
            self._spec_rep = plan.replicated_spec()
            self._spec_state = plan.state_specs(self.pspecs)
            self._ns_lane = NamedSharding(plan.mesh, self._spec_lane)
            self._ns_rep = NamedSharding(plan.mesh, self._spec_rep)
            self._ns_state = jax.tree.map(
                lambda p: NamedSharding(plan.mesh, p), self._spec_state,
                is_leaf=lambda x: isinstance(x, PartitionSpec))
            self.params = jax.device_put(self.params, self._ns_rep)
            self.state = jax.device_put(self.state, self._ns_state)
        #: bytes one contiguous copy_slot moves (the PR 3 hit path cost)
        self.slot_bytes = cache.state_bytes(self.specs) // max_slots
        # resolve() already gated prefix_cache on supports_prefix
        self.prefix = (cache.PrefixTrie(capacity=ecfg.trie_capacity)
                       if ecfg.prefix_cache else None)
        if self.prefix is not None:
            # the scheduler's cost model prices resident prefixes at ~0,
            # so eviction/preemption decisions consult the shared pages
            # (probe only: must not refresh trie recency)
            self.scheduler.reuse_probe = self._probe_reuse
        # content-addressed page dedup: digest -> resident physical pages
        # (resolve() already gated page_dedup on paged_kv)
        self.dedup = (cache.PageDedupIndex() if ecfg.page_dedup else None)
        #: page-content hash, injectable so tests can force collisions
        #: (the share decision never trusts it — a digest match is only a
        #: candidate, confirmed by a full byte compare)
        self._digest_fn = (
            lambda b: hashlib.blake2b(b, digest_size=16).digest())
        #: conversation-id -> accumulated history + retired page refs
        self.sessions = SessionStore()
        #: overload degrade ladder (None = every knob always at its
        #: configured value); thresholds are policy constants, not config
        self.ladder = DegradeLadder() if ecfg.degrade else None
        if self.paged:
            # eviction tie-breaks consult how many pages a victim's
            # release would ACTUALLY free — dedup/prefix-shared pages
            # free nothing until their last referent drops them
            self.scheduler.freed_probe = self._freed_pages
        #: when True, every decode dispatch appends its live-lane fp32
        #: logits to ``logit_trace`` (the bench's quantization-drift probe)
        self.trace_logits = False
        self.logit_trace: List[np.ndarray] = []
        self._exe: Dict[Any, Any] = {}
        self._warm: set = set()
        self._chunk_ewma: Optional[float] = None
        self._step_ewma: Optional[float] = None
        self._tps_ewma: Optional[float] = None
        #: perf_counter readbacks of first tokens in the current step()
        self._first_reads: List[float] = []
        self.reset_stats()

    def _probe_reuse(self, ctx) -> int:
        """Cost-model probe: resident-prefix length of ``ctx`` if it were
        admitted now (0 below the ``min_prefix`` reuse threshold)."""
        n = self.prefix.longest_match(ctx, touch=False)[0]
        n = min(n, max(0, len(ctx) - 1))
        return n if n >= self.min_prefix else 0

    # ------------------------------------------------------------ stats
    def reset_stats(self) -> None:
        """Zero the engine counters/timers (the scheduler's SLO tallies and
        the cost model are NOT reset — they describe the live workload)."""
        self.stats: Dict[str, float] = {
            "prefill_s": 0.0, "decode_s": 0.0,
            "prefill_tokens": 0, "decode_tokens": 0,
            "decode_steps": 0, "decode_lane_steps": 0, "occupancy_sum": 0.0,
            "admissions": 0, "evictions": 0, "preemptions": 0,
            "prefix_hits": 0, "prefix_misses": 0,
            "prefix_reused_tokens": 0, "prefix_evictions": 0,
            # paged-allocation counters (all 0 on contiguous engines
            # except bytes_copied, which prices the copy_slot hit path)
            "prefix_bytes_copied": 0, "pages_shared": 0, "pages_cow": 0,
            "oom_deferred": 0, "hit_admit_s": 0.0, "cold_admit_s": 0.0,
            # speculative-decode counters (all 0 with spec_k == 0)
            "spec_drafted": 0, "spec_accepted": 0,
            "spec_lanes_drafted": 0, "spec_lanes_hit": 0,
            "spec_pages_rolled_back": 0, "spec_steps": 0,
            # tree-speculative counters (all 0 with spec_mode == "chain"):
            # tree-verify dispatches, and the reconfigurator's per-slot
            # per-step shape decisions (chain-shaped vs tree-shaped draft)
            "spec_tree_steps": 0, "spec_shape_chain": 0,
            "spec_shape_tree": 0,
            # page-content dedup counters (all 0 with page_dedup off):
            # admissions that shared >= 1 page by content, whole pages
            # shared that way, and digest matches the byte compare refuted
            "dedup_hits": 0, "dedup_pages_shared": 0,
            "dedup_hash_collisions": 0,
            # multi-turn session counters: turns submitted, re-admissions
            # served from a session snapshot, tokens those reused
            "session_turns": 0, "session_hits": 0,
            "session_reused_tokens": 0, "session_snapshot_drops": 0,
            # degrade-ladder counters (all 0 with degrade off)
            "degrade_steps": 0, "prefill_dispatches": 0,
            # host seconds in the serve loop's phases around the timed
            # prefill_s / decode_s windows (see _phase)
            "admit_plan_s": 0.0, "admit_commit_s": 0.0,
            "decode_prepare_s": 0.0, "decode_commit_s": 0.0,
            # first tokens read back by admissions, and the seconds each
            # waited from its readback to the end of its step()
            "first_token_hold_s": 0.0, "first_tokens": 0,
            # keys attended, summed over prefilled rows and over the live
            # lanes of decode steps (pos + 1 each): the work's context
            "prefill_context_tokens": 0, "decode_context_tokens": 0,
            # sampling dispatches (prefill pieces, decode / spec / tree
            # steps), and those whose every lane was greedy: they take
            # sample_tokens' argmax-only branch
            "sample_dispatches": 0, "sample_greedy_dispatches": 0,
        }
        #: decode lane-steps each mesh shard advanced (index = shard);
        #: a single-device engine accumulates everything in shard 0
        self._shard_lane_steps = np.zeros(max(1, self.shards), np.int64)
        #: recent reconfigurator decisions (slot, accept estimate, shape,
        #: nodes drafted) — stats_summary exposes it as the decision trace
        self._spec_decisions: Deque[Dict[str, Any]] = deque(
            maxlen=_DECISION_TRACE)
        #: per-event latency samples behind the percentile summaries
        #: (sliding windows — see _LATENCY_WINDOW)
        self._step_times: Deque[float] = deque(maxlen=_LATENCY_WINDOW)
        self._admit_times: Dict[str, Deque[float]] = {
            "hit": deque(maxlen=_LATENCY_WINDOW),
            "cold": deque(maxlen=_LATENCY_WINDOW)}

    def _count_sample(self, sps: Sequence[SamplingParams],
                      dispatches: int = 1) -> None:
        """Tally ``dispatches`` sampling dispatches over the lanes ``sps``."""
        self.stats["sample_dispatches"] += dispatches
        if all(p.is_greedy for p in sps):
            self.stats["sample_greedy_dispatches"] += dispatches

    @contextlib.contextmanager
    def _phase(self, name: str, counter: Optional[str] = None):
        """One phase of the serve loop: a profiler span ``name`` (on the
        device trace's clock; about a microsecond with no profiler
        session) that, given a ``counter``, also adds the phase's host
        seconds to ``self.stats[counter]``."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                if counter is not None:
                    self.stats[counter] += time.perf_counter() - t0

    def stats_summary(self) -> Dict[str, float]:
        """Derived view of the counters: tok/s rates, mean occupancy,
        prefix-cache hit rate, *effective* prefill tok/s (reused tokens
        count as served — the uplift a cold engine cannot reach), mean and
        median hit/cold admission latency, decode-step latency percentiles,
        speculative accept-rate / tokens-per-step / draft-hit rates,
        paged-pool usage, trie evictions, and the scheduler's SLO
        met/missed tallies."""
        s = dict(self.stats)
        s["prefill_tok_s"] = s["prefill_tokens"] / max(s["prefill_s"], 1e-9)
        s["decode_tok_s"] = s["decode_tokens"] / max(s["decode_s"], 1e-9)
        s["mean_occupancy"] = (s["occupancy_sum"] / s["decode_steps"]
                               if s["decode_steps"] else 0.0)
        lookups = s["prefix_hits"] + s["prefix_misses"]
        s["prefix_hit_rate"] = s["prefix_hits"] / lookups if lookups else 0.0
        s["effective_prefill_tok_s"] = (
            (s["prefill_tokens"] + s["prefix_reused_tokens"])
            / max(s["prefill_s"], 1e-9))
        s["hit_admit_s_mean"] = (s["hit_admit_s"] / s["prefix_hits"]
                                 if s["prefix_hits"] else 0.0)
        s["cold_admit_s_mean"] = (s["cold_admit_s"] / s["prefix_misses"]
                                  if s["prefix_misses"] else 0.0)
        # medians resist the multi-ms scheduler hiccups that dominate a
        # small hit population's mean on a busy host
        s["hit_admit_s_p50"] = (float(np.median(self._admit_times["hit"]))
                                if self._admit_times["hit"] else 0.0)
        s["cold_admit_s_p50"] = (float(np.median(self._admit_times["cold"]))
                                 if self._admit_times["cold"] else 0.0)
        # decode-step latency percentiles: speculative decode makes steps
        # emit 1..K+1 tokens, so mean tok/s alone hides tail latency
        s["decode_step_p50_s"] = (float(np.percentile(self._step_times, 50))
                                  if self._step_times else 0.0)
        s["decode_step_p99_s"] = (float(np.percentile(self._step_times, 99))
                                  if self._step_times else 0.0)
        # speculative decode: accepted drafts per proposed draft, emitted
        # tokens per live lane per step (1.0 for sequential decode), and
        # the fraction of drafted lanes where >= 1 draft survived
        s["spec_accept_rate"] = (s["spec_accepted"] / s["spec_drafted"]
                                 if s["spec_drafted"] else 0.0)
        s["tokens_per_step"] = (s["decode_tokens"] / s["decode_lane_steps"]
                                if s["decode_lane_steps"] else 0.0)
        s["spec_draft_hit_rate"] = (
            s["spec_lanes_hit"] / s["spec_lanes_drafted"]
            if s["spec_lanes_drafted"] else 0.0)
        s["spec_k"] = self.spec_k
        # tree speculative decode: the resolved topology knobs, accept-rate
        # percentiles over the per-slot EWMAs (the reconfigurator's inputs
        # — a flat global rate hides the spread the auto policy exploits),
        # and the recent shape-decision trace
        s["spec_mode"] = self.spec_mode
        s["spec_tree_nodes"] = self.spec_tree_nodes
        s["spec_branch"] = self.spec_branch
        s["spec_drafter"] = self.spec_drafter
        accepts = sorted(max(d.values()) for d in
                         self._slot_accept.values() if d)
        s["spec_accept_p50"] = (float(np.percentile(accepts, 50))
                                if accepts else 0.0)
        s["spec_accept_p99"] = (float(np.percentile(accepts, 99))
                                if accepts else 0.0)
        s["spec_decision_trace"] = list(self._spec_decisions)
        s["trie_evictions"] = (self.prefix.evictions
                               if self.prefix is not None else 0)
        s["pages_in_use"] = self.pool.used_count if self.paged else 0
        s["pool_pages"] = (self.pool.num_pages - self.pool.shards
                           if self.paged else 0)
        # capacity accounting for the kv_dtype knob: bytes one resident
        # slot's full KV row occupies, and the whole pool's footprint —
        # quantized pages shrink both at fixed page counts
        s["kv_dtype"] = self.kv_dtype
        s["kv_bytes_per_slot"] = (self.page_bytes * self.max_pages
                                  if self.paged else self.slot_bytes)
        s["pool_bytes"] = cache.state_bytes(
            self.pspecs if self.paged else self.specs)
        s["slo_met"] = self.scheduler.slo_met_count
        s["slo_missed"] = self.scheduler.slo_missed_count
        # overload accounting: goodput counts only tokens of retired
        # requests that did not miss their SLO, over engine busy time
        # (an open-loop driver measuring wall time divides by its own
        # elapsed instead — see benchmarks/bench_serve.py)
        s["shed_requests"] = self.scheduler.shed_count
        s["goodput_tokens"] = self.scheduler.goodput_tokens
        s["goodput_tok_s"] = self.scheduler.goodput_tokens / max(
            s["prefill_s"] + s["decode_s"], 1e-9)
        s["degrade_level"] = (self.ladder.level
                              if self.ladder is not None else 0)
        s["degrade_transitions"] = (self.ladder.transitions
                                    if self.ladder is not None else 0)
        # dedup rates: content hits per admission, pages shared per hit
        s["dedup_hit_rate"] = (s["dedup_hits"] / s["admissions"]
                               if s["admissions"] else 0.0)
        s["dedup_pages_per_hit"] = (s["dedup_pages_shared"]
                                    / s["dedup_hits"]
                                    if s["dedup_hits"] else 0.0)
        s["dedup_indexed_pages"] = (len(self.dedup)
                                    if self.dedup is not None else 0)
        s["sessions_live"] = len(self.sessions)
        # mesh-sharded serving: decode lanes each shard advanced, and the
        # relative spread between the busiest and idlest shard (0.0 =
        # perfectly balanced admission; trivially 0.0 single-device)
        s["mesh_shards"] = self.shards
        lane_steps = self._shard_lane_steps
        s["shard_lane_steps"] = [int(x) for x in lane_steps]
        peak = int(lane_steps.max()) if lane_steps.size else 0
        s["shard_occupancy_skew"] = (
            float((int(lane_steps.max()) - int(lane_steps.min())) / peak)
            if peak else 0.0)
        return s

    # ------------------------------------------------- mesh-sharded plumbing
    def _slot_shard(self, slot: int) -> int:
        """The mesh shard owning ``slot`` (always 0 single-device)."""
        if self.mesh_plan is None:
            return 0
        return self.mesh_plan.shard_of_slot(slot)

    def _put_lane(self, x):
        """Commit a per-slot lane array to its ``P("slots")`` placement.
        AOT-compiled dispatches check input shardings, so per-call inputs
        must arrive pre-placed; identity on single-device engines."""
        arr = jnp.asarray(x)
        if self.mesh_plan is None:
            return arr
        return jax.device_put(arr, self._ns_lane)

    def _put_rep(self, x):
        """Commit a broadcast scalar/array to the replicated placement
        (identity on single-device engines)."""
        arr = jnp.asarray(x)
        if self.mesh_plan is None:
            return arr
        return jax.device_put(arr, self._ns_rep)

    def _local_disp(self, disp: np.ndarray) -> np.ndarray:
        """Localize a dispatch page table: global page ids -> shard-local
        block offsets (identity single-device, where global IS local)."""
        if self.mesh_plan is None:
            return disp
        return self.mesh_plan.local_pages(disp)

    # ----------------------------------------------------- compiled fns
    def _sds(self, shape, dtype, *, lane: bool = False):
        """ShapeDtypeStruct for AOT lowering, carrying the mesh sharding
        on sharded engines (lowering against committed input layouts is
        what lets the compiled dispatch skip every resharding check)."""
        if self.mesh_plan is None:
            return jax.ShapeDtypeStruct(shape, dtype)
        ns = self._ns_lane if lane else self._ns_rep
        return jax.ShapeDtypeStruct(shape, dtype, sharding=ns)

    def _params_structs(self):
        structs = shape_structs(self.params)   # works on array leaves too
        if self.mesh_plan is not None:
            structs = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=self._ns_rep),
                structs)
        return structs

    def _get(self, key, fn, *arg_structs):
        """AOT-compile on first use; compile time never enters the timers.
        The executable's module is named by its key, so a trace tells the
        prefill buckets apart: ``"decode"`` traces as ``jit_decode``,
        ``("prefill", 512)`` as ``jit_prefill_b512``."""
        if key not in self._exe:
            fn.__name__ = key if isinstance(key, str) else "%s_b%d" % key
            self._exe[key] = jax.jit(fn).lower(*arg_structs).compile()
        return self._exe[key]

    def _ensure_warm(self, key, exe, *args) -> None:
        """Execute a compiled function once, untimed, before its first timed
        use: XLA's first execution pays one-time thunk/kernel setup that is
        compile cost in all but name. The functions are pure, so a discarded
        extra execution is semantically free."""
        if key in self._warm:
            return
        jax.block_until_ready(exe(*args))
        self._warm.add(key)

    def _reset_exe(self):
        def reset(state, slot):
            return cache.reset_slot(state, self.specs, slot)
        return self._get(
            "reset", reset, shape_structs(self.specs),
            jax.ShapeDtypeStruct((), jnp.int32))

    def _copy_exe(self):
        def copy(state, src, dst):
            return cache.copy_slot(state, self.specs, src, dst)
        i32 = jnp.int32
        return self._get(
            "copy", copy, shape_structs(self.specs),
            jax.ShapeDtypeStruct((), i32), jax.ShapeDtypeStruct((), i32))

    def _page_copy_exe(self):
        """Boundary-page copy-on-write: one physical page, every leaf.
        Sharded engines dispatch it under shard_map with per-shard (1,)
        src/dst lanes of shard-local ids — non-target shards are fed
        (0, 0), a scratch self-copy no-op."""
        i32 = jnp.int32
        if self.mesh_plan is None:
            def copy(state, src, dst):
                return cache.copy_page(state, self.pspecs, src, dst)
            return self._get(
                "page_copy", copy, self._state_structs(),
                jax.ShapeDtypeStruct((), i32), jax.ShapeDtypeStruct((), i32))

        def copy(state, src, dst):
            return cache.copy_page(state, self.pspecs, src[0], dst[0])
        copy = jax.shard_map(
            copy, mesh=self.mesh_plan.mesh,
            in_specs=(self._spec_state, self._spec_lane, self._spec_lane),
            out_specs=self._spec_state)
        lane = self._sds((self.shards,), i32, lane=True)
        return self._get("page_copy", copy, self._state_structs(),
                         lane, lane)

    def _scrub_exe(self):
        """Zero the scratch page(s): page 0 single-device, every shard's
        local page 0 sharded.  Dispatched after each admission wave so the
        bytes masked lanes read through scratch — prefill-broadcast and
        idle-lane garbage that perturbs only split-K rounding, never a
        masked value — are identical whatever engine layout served the
        prefills (the sharded-vs-single bit-exactness contract)."""
        def scrub(state):
            return cache.zero_page(state, self.pspecs, 0)
        if self.mesh_plan is not None:
            scrub = jax.shard_map(
                scrub, mesh=self.mesh_plan.mesh,
                in_specs=(self._spec_state,), out_specs=self._spec_state)
        return self._get("scrub", scrub, self._state_structs())

    def _scrub_scratch(self) -> None:
        """Dispatch the scratch scrub (untimed — bookkeeping, not serving).
        Runs after warmup and after every admission's prefill pieces, so
        each prefill — wherever it broadcasts — reads all-zeros scratch."""
        exe = self._scrub_exe()
        self._ensure_warm("scrub", exe, self.state)
        self.state = exe(self.state)

    def _state_structs(self):
        structs = shape_structs(self.pspecs if self.paged else self.specs)
        if self.mesh_plan is not None:
            structs = jax.tree.map(
                lambda s, ns: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=ns),
                structs, self._ns_state)
        return structs

    def _prefill_exe(self, cb: int):
        if self.paged and self.mesh_plan is not None:
            def prefill(params, state, tokens, pages, start, nvalid,
                        temp, top_k, top_p, seed, sidx):
                # per-shard body: ``pages`` is this shard's (1, max_pages)
                # row of shard-local ids — the target shard gets the
                # slot's real row, every other shard an all-scratch row
                # (their writes land on scratch, their sampled lane is
                # discarded by the host)
                with jax.named_scope("model"):
                    logits, state = self.api.prefill_chunk(
                        params, state,
                        {"tokens": tokens, "index": start, "nvalid": nvalid,
                         "pages": pages},
                        self.cfg)
                with jax.named_scope("sample"):
                    nxt = sample_tokens(logits, temp[None], top_k[None],
                                        top_p[None], seed[None], sidx[None])
                return nxt, logits, state
            prefill = jax.shard_map(
                prefill, mesh=self.mesh_plan.mesh,
                in_specs=(self._spec_rep, self._spec_state,
                          self._spec_rep, self._spec_lane,
                          *(self._spec_rep,) * 7),
                out_specs=(self._spec_lane, self._spec_lane,
                           self._spec_state))
            extra = self._sds((self.shards, self.max_pages), jnp.int32,
                              lane=True)
        elif self.paged:
            def prefill(params, state, tokens, pages, start, nvalid,
                        temp, top_k, top_p, seed, sidx):
                with jax.named_scope("model"):
                    logits, state = self.api.prefill_chunk(
                        params, state,
                        {"tokens": tokens, "index": start, "nvalid": nvalid,
                         "pages": pages[None]},
                        self.cfg)
                with jax.named_scope("sample"):
                    nxt = sample_tokens(logits, temp[None], top_k[None],
                                        top_p[None], seed[None], sidx[None])
                return nxt, logits, state
            extra = jax.ShapeDtypeStruct((self.max_pages,), jnp.int32)
        else:
            def prefill(params, state, tokens, slot, start, nvalid,
                        temp, top_k, top_p, seed, sidx):
                slot_state = cache.slot_slice(state, self.specs, slot)
                with jax.named_scope("model"):
                    logits, new_slot = self.api.prefill_chunk(
                        params, slot_state,
                        {"tokens": tokens, "index": start, "nvalid": nvalid},
                        self.cfg)
                state = cache.slot_update(state, self.specs, slot, new_slot)
                with jax.named_scope("sample"):
                    nxt = sample_tokens(logits, temp[None], top_k[None],
                                        top_p[None], seed[None], sidx[None])
                return nxt, logits, state
            extra = jax.ShapeDtypeStruct((), jnp.int32)
        i32, f32 = jnp.int32, jnp.float32
        sc = self._sds((), i32)
        sf = self._sds((), f32)
        return self._get(
            ("prefill", cb), prefill, self._params_structs(),
            self._state_structs(),
            self._sds((1, cb), i32),
            extra, sc, sc, sf, sc, sf, sc, sc)

    def _decode_exe(self):
        if self.paged:
            def decode(params, state, tokens, positions, pages,
                       temps, top_ks, top_ps, seeds, idxs):
                with jax.named_scope("model"):
                    logits, state = self.api.decode_step(
                        params, state,
                        {"tokens": tokens, "index": positions,
                         "pages": pages},
                        self.cfg)
                with jax.named_scope("sample"):
                    nxt = sample_tokens(logits, temps, top_ks, top_ps,
                                        seeds, idxs)
                return nxt, logits, state
            extra = (self._sds((self.max_slots, self.max_pages), jnp.int32,
                               lane=True),)
        else:
            def decode(params, state, tokens, positions,
                       temps, top_ks, top_ps, seeds, idxs):
                with jax.named_scope("model"):
                    logits, state = self.api.decode_step(
                        params, state,
                        {"tokens": tokens, "index": positions}, self.cfg)
                with jax.named_scope("sample"):
                    nxt = sample_tokens(logits, temps, top_ks, top_ps,
                                        seeds, idxs)
                return nxt, logits, state
            extra = ()
        if self.mesh_plan is not None:
            # every per-slot input shards along "slots"; no collective
            # appears in the body, so a sharded decode step moves zero
            # cross-device bytes — each device advances only its own lanes
            lane = self._spec_lane
            decode = jax.shard_map(
                decode, mesh=self.mesh_plan.mesh,
                in_specs=(self._spec_rep, self._spec_state, *(lane,) * 8),
                out_specs=(lane, lane, self._spec_state))
        i32, f32 = jnp.int32, jnp.float32
        b = self.max_slots
        lane_i = self._sds((b,), i32, lane=True)
        lane_f = self._sds((b,), f32, lane=True)
        return self._get(
            "decode", decode, self._params_structs(),
            self._state_structs(),
            self._sds((b, 1), i32, lane=True), lane_i, *extra,
            lane_f, lane_i, lane_f, lane_i, lane_i)

    def _spec_exe(self):
        """One speculative decode step: verify the (B, K+1) drafted block
        in a single dispatch and sample a token at EVERY fed position —
        column ``j`` draws with sample index ``idxs + j``, so each column
        is exactly the draw sequential decode would make at that index."""
        kp1 = self.spec_k + 1

        def sample_block(logits, temps, top_ks, top_ps, seeds, idxs):
            # one flattened (B*(K+1),)-lane sampling pass instead of K+1
            # per-column passes: column j of slot b draws with sample
            # index idxs[b] + j — exactly the draw sequential decode
            # makes at that index, in one vmapped dispatch
            b, v = logits.shape[0], logits.shape[-1]
            rep = lambda lane: jnp.repeat(lane, kp1)
            col_idx = (idxs[:, None]
                       + jnp.arange(kp1, dtype=jnp.int32)[None]).reshape(-1)
            toks = sample_tokens(logits.reshape(b * kp1, v), rep(temps),
                                 rep(top_ks), rep(top_ps), rep(seeds),
                                 col_idx)
            return toks.reshape(b, kp1)

        if self.paged:
            def spec(params, state, tokens, positions, pages, nspec,
                     temps, top_ks, top_ps, seeds, idxs):
                with jax.named_scope("model"):
                    logits, state = self.api.verify_chunk(
                        params, state,
                        {"tokens": tokens, "index": positions,
                         "pages": pages, "nspec": nspec}, self.cfg)
                with jax.named_scope("sample"):
                    toks = sample_block(logits, temps, top_ks, top_ps,
                                        seeds, idxs)
                return toks, logits, state
            extra = (self._sds((self.max_slots, self.max_pages), jnp.int32,
                               lane=True),)
        else:
            def spec(params, state, tokens, positions, nspec,
                     temps, top_ks, top_ps, seeds, idxs):
                with jax.named_scope("model"):
                    logits, state = self.api.verify_chunk(
                        params, state,
                        {"tokens": tokens, "index": positions,
                         "nspec": nspec}, self.cfg)
                with jax.named_scope("sample"):
                    toks = sample_block(logits, temps, top_ks, top_ps,
                                        seeds, idxs)
                return toks, logits, state
            extra = ()
        if self.mesh_plan is not None:
            lane = self._spec_lane
            spec = jax.shard_map(
                spec, mesh=self.mesh_plan.mesh,
                in_specs=(self._spec_rep, self._spec_state, *(lane,) * 9),
                out_specs=(lane, lane, self._spec_state))
        i32, f32 = jnp.int32, jnp.float32
        b = self.max_slots
        lane_i = self._sds((b,), i32, lane=True)
        lane_f = self._sds((b,), f32, lane=True)
        return self._get(
            "spec", spec, self._params_structs(), self._state_structs(),
            self._sds((b, kp1), i32, lane=True), lane_i, *extra, lane_i,
            lane_f, lane_i, lane_f, lane_i, lane_i)

    def _tree_width(self) -> int:
        """Static row width of the tree-verify dispatch: the widest chain
        part plus the drafted-node budget.  Because drafting depth is
        capped (``nodes // branch`` for the n-gram tree, ``n_heads`` for
        draft heads), an accepted root-to-leaf path — the NEXT step's
        chain part — is at most ``depth + 1`` tokens, so the width is
        ``(depth_cap + 1) + nodes``, much narrower than the naive
        ``2 * nodes + 1``.  Auto mode additionally sizes for a chain-
        ``spec_k`` per-slot shape (drafts ``spec_k`` nodes, accepts up to
        ``spec_k + 1``), so one compiled executable serves every per-slot
        shape decision."""
        n = self.spec_tree_nodes
        d = max(1, n // max(self.spec_branch, 1))
        if self.head_drafter is not None:
            d = max(d, self.head_drafter.n_heads)
        if self.spec_mode == "auto":
            return max(d, self.spec_k) + 1 + max(n, self.spec_k)
        return d + 1 + n

    def _tree_exe(self):
        """One tree-speculative decode step: verify a (B, C) block — each
        slot's ``nchain`` unmaterialized chain tokens followed by its
        drafted tree rows — in a single ``verify_tree`` dispatch and
        sample a token at EVERY row.  Row ``j`` draws with sample index
        ``idxs + pos_off[j] - (nchain - 1)``: the anchor (last chain row)
        draws at the slot's next sequential index and a depth-``d`` node
        at index ``+ d``, so whichever root-to-leaf path is accepted, its
        samples are exactly the sequential draws at those indices."""
        cw = self._tree_width()
        heads_on = self.head_drafter is not None

        def sample_block(logits, pos_off, nchain, temps, top_ks, top_ps,
                         seeds, idxs):
            b, v = logits.shape[0], logits.shape[-1]
            rep = lambda lane: jnp.repeat(lane, cw)
            # chain rows before the anchor re-derive already-emitted
            # indices (clamped >= 0); their samples are discarded
            col_idx = jnp.maximum(
                idxs[:, None] + pos_off - (nchain[:, None] - 1),
                0).astype(jnp.int32).reshape(-1)
            toks = sample_tokens(logits.reshape(b * cw, v), rep(temps),
                                 rep(top_ks), rep(top_ps), rep(seeds),
                                 col_idx)
            return toks.reshape(b, cw)

        def body(params, state, tokens, positions, pages, parents, pos_off,
                 nchain, nspec, temps, top_ks, top_ps, seeds, idxs):
            batch = {"tokens": tokens, "index": positions,
                     "parents": parents, "pos_off": pos_off,
                     "nchain": nchain, "nspec": nspec}
            if pages is not None:
                batch["pages"] = pages
            with jax.named_scope("model"):
                logits, head_top, state = self.api.verify_tree(
                    params, state, batch, self.cfg,
                    head_topk=self.spec_branch)
            with jax.named_scope("sample"):
                toks = sample_block(logits, pos_off, nchain, temps, top_ks,
                                    top_ps, seeds, idxs)
            if not heads_on:
                # stable output structure: a 1-element dummy when the
                # drafter never reads head candidates
                head_top = jnp.zeros((tokens.shape[0], 1, 1, 1), jnp.int32)
            return toks, head_top, logits, state

        if self.paged:
            def tree(params, state, tokens, positions, pages, *rest):
                return body(params, state, tokens, positions, pages, *rest)
            extra = (self._sds((self.max_slots, self.max_pages), jnp.int32,
                               lane=True),)
        else:
            def tree(params, state, tokens, positions, *rest):
                return body(params, state, tokens, positions, None, *rest)
            extra = ()
        if self.mesh_plan is not None:
            lane = self._spec_lane
            n_lanes = 12 if self.paged else 11
            tree = jax.shard_map(
                tree, mesh=self.mesh_plan.mesh,
                in_specs=(self._spec_rep, self._spec_state,
                          *(lane,) * n_lanes),
                out_specs=(lane, lane, lane, self._spec_state))
        i32, f32 = jnp.int32, jnp.float32
        b = self.max_slots
        lane_i = self._sds((b,), i32, lane=True)
        lane_f = self._sds((b,), f32, lane=True)
        mat_i = self._sds((b, cw), i32, lane=True)
        return self._get(
            "tree", tree, self._params_structs(), self._state_structs(),
            mat_i, lane_i, *extra, mat_i, mat_i, lane_i, lane_i,
            lane_f, lane_i, lane_f, lane_i, lane_i)

    def _greedy_lanes(self, b: int):
        return sampling_lanes([GREEDY] * b, [0] * b)

    def warmup(self) -> None:
        """Force every compilation AND first execution up front (optional;
        the engine also warms lazily, still outside the timed regions).
        Paged engines warm with all-scratch page tables, so the warmup
        writes land only on the reserved scratch page."""
        i32, f32 = jnp.int32, jnp.float32
        z = self._put_rep(jnp.asarray(0, i32))
        zf = self._put_rep(jnp.asarray(0.0, f32))
        onef = self._put_rep(jnp.asarray(1.0, f32))
        if self.paged:
            if self.mesh_plan is None:
                pc_args = (z, z)
                prefill_extra = jnp.zeros((self.max_pages,), i32)
            else:
                # all-zero lanes: every shard self-copies / writes only
                # its own local scratch page
                lane0 = self._put_lane(np.zeros(self.shards, np.int32))
                pc_args = (lane0, lane0)
                prefill_extra = self._put_lane(
                    np.zeros((self.shards, self.max_pages), np.int32))
            if self.prefix is not None:
                self._ensure_warm("page_copy", self._page_copy_exe(),
                                  self.state, *pc_args)
            decode_extra = (self._put_lane(
                jnp.zeros((self.max_slots, self.max_pages), i32)),)
        else:
            self._ensure_warm("reset", self._reset_exe(), self.state, z)
            if self.prefix is not None:
                self._ensure_warm("copy", self._copy_exe(), self.state, z, z)
            prefill_extra = z
            decode_extra = ()
        self._ensure_warm(
            "decode", self._decode_exe(), self.params, self.state,
            self._put_lane(jnp.zeros((self.max_slots, 1), i32)),
            self._put_lane(jnp.zeros((self.max_slots,), i32)), *decode_extra,
            *(self._put_lane(a) for a in self._greedy_lanes(self.max_slots)))
        if self.spec_k and self.spec_mode == "chain":
            # all-idle warmup block: nspec = 0 masks every cache write
            self._ensure_warm(
                "spec", self._spec_exe(), self.params, self.state,
                self._put_lane(jnp.zeros((self.max_slots, self.spec_k + 1),
                                         i32)),
                self._put_lane(jnp.zeros((self.max_slots,), i32)),
                *decode_extra,
                self._put_lane(jnp.zeros((self.max_slots,), i32)),
                *(self._put_lane(a)
                  for a in self._greedy_lanes(self.max_slots)))
        if self.spec_mode != "chain":
            cw = self._tree_width()
            lane0 = self._put_lane(jnp.zeros((self.max_slots,), i32))
            # padding rows parent themselves: never anyone's ancestor
            self._ensure_warm(
                "tree", self._tree_exe(), self.params, self.state,
                self._put_lane(jnp.zeros((self.max_slots, cw), i32)),
                lane0, *decode_extra,
                self._put_lane(np.broadcast_to(
                    np.arange(cw, dtype=np.int32),
                    (self.max_slots, cw)).copy()),
                self._put_lane(jnp.zeros((self.max_slots, cw), i32)),
                lane0, lane0,
                *(self._put_lane(a)
                  for a in self._greedy_lanes(self.max_slots)))
        for cb in self.chunk_buckets:
            self._ensure_warm(
                ("prefill", cb), self._prefill_exe(cb), self.params,
                self.state, self._put_rep(jnp.zeros((1, cb), i32)),
                prefill_extra, z, self._put_rep(jnp.asarray(cb, i32)), zf,
                z, onef, z, z)
        if self.paged:
            self._scrub_scratch()

    # ----------------------------------------------------------- submit
    def submit(self, prompt: Sequence[int], max_new: int,
               eos_id: Optional[int] = None,
               sampling: Optional[SamplingParams] = None,
               slo_ms: Optional[float] = None) -> Request:
        """Queue one generation request.

        Args:
          prompt: token ids to condition on.
          max_new: generation budget.
          eos_id: optional stop token.
          sampling: per-request :class:`SamplingParams` (``None`` = greedy).
          slo_ms: optional completion-latency SLO in milliseconds.

        Returns:
          The live :class:`Request` handle (its ``generated`` list fills in
          as the engine runs)."""
        return self.scheduler.submit(
            Request(prompt=list(prompt), max_new=max_new, eos_id=eos_id,
                    sampling=sampling, slo_ms=slo_ms))

    # --------------------------------------------------------- sessions
    def submit_turn(self, conv_id, tokens: Sequence[int], max_new: int,
                    eos_id: Optional[int] = None,
                    sampling: Optional[SamplingParams] = None,
                    slo_ms: Optional[float] = None) -> Request:
        """Queue one turn of conversation ``conv_id``: the prompt is the
        conversation's accumulated history (every previous turn's prompt
        + reply) plus the new ``tokens``.  On a paged engine a returning
        conversation re-admits its history as *shared pages* from the
        session's retired page snapshot — full pages by reference, one
        boundary page copy-on-write — even after every slot has turned
        over; the reply is appended to the history when the turn
        retires.  ``max_new``, ``eos_id``, ``sampling``, ``slo_ms`` and
        the return value match :meth:`submit`."""
        sess = self.sessions.ensure(conv_id)
        req = self.submit(list(sess.history) + list(tokens), max_new,
                          eos_id=eos_id, sampling=sampling, slo_ms=slo_ms)
        req._conv_id = conv_id
        self.stats["session_turns"] += 1
        return req

    def end_session(self, conv_id) -> bool:
        """Drop conversation ``conv_id``: release its retired-page
        snapshot (if any) and forget its history.  Returns True if the
        session existed."""
        existed = conv_id in self.sessions
        row = self.sessions.pop(conv_id)
        if row is not None:
            self._deref_row_pages(row[row != 0])
        return existed

    def _session_retire(self, req: Request, slot: int) -> None:
        """A session turn just retired out of ``slot``: fold its reply
        into the conversation history and (paged engines) snapshot the
        slot's page row — one pool reference per page — so the history
        stays resident for the next turn.  Replaces (and releases) any
        previous snapshot; called after speculative rollback, so the row
        maps exactly the ``req.pos`` materialized positions."""
        conv = getattr(req, "_conv_id", None)
        if conv is None:
            return
        sess = self.sessions.ensure(conv)
        sess.history = req.context
        sess.turns += 1
        if not self.paged:
            return
        old = self.sessions.take_snapshot(sess)
        if old is not None:
            self._deref_row_pages(old[old != 0])
        # materialized positions in the row: req.pos for chain decode (one
        # unwritten token), fewer under tree decode where the final step's
        # whole accepted path retires unmaterialized
        covered = req.pos + 1 - self._spec_unwritten.get(slot, 1)
        npages = -(-covered // self.page_size)
        row = self.table[slot, :npages].copy()
        if covered > 0 and int((row != 0).sum()) == npages:
            self.pool.ref_many(row)
            sess.row = row
            sess.covered = covered

    def evict(self, slot: int) -> Request:
        """Preempt the live request in ``slot`` back to the pending queue
        (its re-admission re-prefills, or prefix-reuses, its context).
        On a paged engine the slot's pages are released immediately when
        nothing can reuse them (no prefix cache, or the slot's trie entry
        was already LRU-evicted while it was live)."""
        self.stats["evictions"] += 1
        req = self.scheduler.evict(slot)
        if self.paged and not self._row_reusable(slot):
            self._release_row(slot)
        return req

    def _row_reusable(self, slot: int) -> bool:
        """True while ``slot``'s pages are worth keeping after its request
        leaves: a trie entry still indexes them for prefix reuse.  Without
        one the row would be invisible to LRU reclaim (which scans trie
        entries) and its pages would strand until the slot is reused."""
        return self.prefix is not None and \
            self.prefix.length(slot) is not None

    # ----------------------------------------------- page-table management
    def _deref_row_pages(self, pages: np.ndarray) -> int:
        """Deref ``pages`` and un-index every one that actually freed from
        the dedup index (an indexed page must always be resident — the
        invariant the churn suite checks); returns pages freed."""
        pages = np.asarray(pages)
        freed = self.pool.deref_many(pages)
        if freed and self.dedup is not None:
            for p in np.unique(pages):
                if self.pool.refcount[p] == 0:
                    self.dedup.discard(int(p))
        return freed

    def _freed_pages(self, slot: int) -> int:
        """How many physical pages releasing ``slot``'s row would actually
        free right now: pages some other row (or a session snapshot, or a
        dedup referent) still holds stay resident and free nothing.  The
        scheduler's eviction tie-break consults this so it does not thrash
        shared pages."""
        row = self.table[slot]
        pages = row[row != 0]
        if not pages.size:
            return 0
        uniq, counts = np.unique(pages, return_counts=True)
        return int((self.pool.refcount[uniq] == counts).sum())

    def _release_row(self, slot: int) -> None:
        """Drop slot's page-table row: deref every mapped page in one
        vectorized call (a page shared with another row survives — its
        refcount stays positive) and drop the now-stale trie entry."""
        if self.prefix is not None:
            self.prefix.remove(slot)
        row = self.table[slot]
        self._deref_row_pages(row[row != 0])
        self.table[slot] = 0

    def _release_trie_evicted(self, slots) -> None:
        """Release the rows of LRU-evicted trie ``slots`` that are not
        live (their pages were only being kept for reuse)."""
        for s in slots:
            if s not in self.scheduler.active:
                self._release_row(s)

    def _reclaim_pages(self, needed: int, shard: int = 0) -> None:
        """Free pages under pool pressure, cheapest-first, until ``needed``
        pages are free in ``shard``'s block (or nothing reclaimable
        remains):

        1. retired trie entries, least-recently-used first — but entries
           whose release would free *zero* pages (every page still shared
           by another row, a dedup referent, or a session snapshot) go
           last: dropping them costs future reuse and reclaims nothing;
        2. then session snapshots, least-recently-used first (correctness
           survives — the conversation's next turn just re-prefills).

        Only victims whose pages live in ``shard``'s block are released —
        freeing another shard's pages cannot satisfy this allocation.
        Live slots are never touched."""
        if self.prefix is not None:
            victims = [s for s in self.prefix.lru_slots()
                       if s not in self.scheduler.active
                       and self._slot_shard(s) == shard]
            victims.sort(key=lambda s: self._freed_pages(s) == 0)
            for s in victims:
                if self.pool.free_count_in(shard) >= needed:
                    return
                self._release_row(s)
                self.prefix.evictions += 1
        for sess in self.sessions.lru_snapshots():
            if self.pool.free_count_in(shard) >= needed:
                return
            if self.mesh_plan is not None and \
                    self.pool.shard_of(int(sess.row[0])) != shard:
                continue
            row = self.sessions.take_snapshot(sess)
            self._deref_row_pages(row[row != 0])
            self.sessions.drops += 1
            self.stats["session_snapshot_drops"] += 1

    def _ensure_pages(self, slot: int, start: int, end: int) -> bool:
        """Lazily allocate physical pages covering positions ``[start,
        end)`` of ``slot``'s row (reclaiming LRU retired entries under
        pressure). Allocation is process-local to the slot's own shard
        block — admission never does a cross-shard allocator round-trip.
        One vectorized all-or-nothing allocation — no per-page Python
        loop, and nothing to roll back on exhaustion. Returns False when
        the shard's block is exhausted."""
        first = start // self.page_size
        last = min(-(-end // self.page_size), self.max_pages)
        need = first + np.flatnonzero(self.table[slot, first:last] == 0)
        sh = self._slot_shard(slot)
        if need.size > self.pool.free_count_in(sh):
            self._reclaim_pages(int(need.size), sh)
        if need.size:
            pages = self.pool.alloc_many(int(need.size), sh)
            if pages is None:
                return False
            self.table[slot, need] = pages
        return True

    def _rollback_pages(self, slot: int, length: int) -> None:
        """Rewind ``slot``'s row after speculative rejection: release every
        mapped page wholly past the accepted ``length`` (those pages hold
        only rejected-draft garbage, never attended because every position
        at/after ``length`` is causally masked).  Shared prefix pages can
        never be hit — sharing stops below the slot's write frontier — so
        each release is the deref of this row's own reference: refcounts
        stay exactly conserved with the table."""
        first = -(-length // self.page_size)
        row = self.table[slot]
        stale = first + np.flatnonzero(row[first:] != 0)
        if stale.size:
            self._deref_row_pages(row[stale])
            row[stale] = 0
            self.stats["spec_pages_rolled_back"] += int(stale.size)

    def _bind_pages(self, slot: int, src_row: Optional[np.ndarray],
                    reuse: int, end: int, *, in_place: bool = False
                    ) -> Tuple[bool, Optional[Tuple[int, int]]]:
        """Build ``slot``'s page-table row for an admission reusing the
        first ``reuse`` tokens materialized in ``src_row`` (another slot's
        table row, or a session snapshot), with writable pages through
        position ``end``: full prefix pages are shared by *reference*
        (refcount bump — zero bytes), the partial boundary page gets a
        fresh destination for copy-on-write, and the prefill span is
        allocated lazily.

        ``in_place`` marks a re-admission into the slot whose own pages
        already hold the prefix (``src_row`` is ignored).  The row is kept,
        but prefill is about to overwrite every position >= ``reuse`` —
        and any page there with refcount > 1 is *shared* (another row, a
        session snapshot, or a dedup referent holds it), so writing
        through it would corrupt the sharer's view.  Those pages are
        detached first: the partial boundary page by copy-on-write, fully
        rewritten pages by a fresh replacement (their old bytes are never
        read through this row again).

        Returns ``(ok, cow)`` — ``cow`` is the ``(src_phys, dst_phys)``
        boundary copy the caller must dispatch (or None), and ``ok`` is
        False when the pool is exhausted (the row is rolled back and the
        admission should be deferred)."""
        ps = self.page_size
        sh = self._slot_shard(slot)
        cow = None
        nfull = 0
        if reuse and not in_place:
            self._release_row(slot)
            nfull = reuse // ps
            # share the whole full-page span in two vectorized ops: one
            # refcount scatter, one row assignment (the hit path must not
            # pay a per-page Python loop)
            shared = np.asarray(src_row[:nfull])
            self.pool.ref_many(shared)
            self.table[slot, :nfull] = shared
            if reuse % ps:
                # snapshot the source boundary page BEFORE any reclaim can
                # release src's row; even if reclaim frees it, its bytes
                # stay intact until the CoW copy (the first device write
                # of this admission) has read them
                src_b = int(src_row[nfull])
                if self.pool.free_count_in(sh) < 1:
                    self._reclaim_pages(1, sh)
                p = self.pool.alloc(sh)
                if p < 0:
                    self._release_row(slot)
                    return False, None
                self.table[slot, nfull] = p
                cow = (src_b, p)
        elif not reuse:
            self._release_row(slot)
        else:
            # in-place reuse: detach the overwrite span from any sharers
            row = self.table[slot]
            first = reuse // ps
            for j in range(first, self.max_pages):
                p = int(row[j])
                if p == 0:
                    continue
                partial = (j == first and reuse % ps)
                if self.pool.refcount[p] > 1:
                    if self.pool.free_count_in(sh) < 1:
                        self._reclaim_pages(1, sh)
                    fresh = self.pool.alloc(sh)
                    if fresh < 0:
                        self._release_row(slot)
                        return False, None
                    if partial:
                        # positions [j*ps, reuse) must survive the swap
                        cow = (p, fresh)
                    row[j] = fresh
                    self._deref_row_pages(np.asarray([p]))
                elif self.dedup is not None:
                    # kept-and-(partially-)rewritten page: its content is
                    # about to change, so its index entry must die NOW
                    self.dedup.discard(p)
        if not self._ensure_pages(slot, reuse, end):
            self._release_row(slot)
            return False, None
        self.stats["pages_shared"] += nfull
        return True, cow

    # ---------------------------------------------------- content dedup
    def _page_bytes_of(self, page: int) -> bytes:
        """The raw bytes of ONE physical page across every pooled leaf
        (codes AND their fp32 scale siblings for quantized pools), in
        deterministic leaf order — the unit of content identity.  Only
        the page is transferred off-device, not the pool."""
        specs = jax.tree.leaves(self.pspecs,
                                is_leaf=lambda x: isinstance(x, ParamSpec))
        leaves = jax.tree.leaves(self.state)
        chunks = []
        for leaf, spec in zip(leaves, specs):
            ax = spec.axes.index("phys_page")
            arr = jax.lax.index_in_dim(leaf, page, axis=ax, keepdims=False)
            chunks.append(np.asarray(arr).tobytes())
        return b"".join(chunks)

    def _dedup_slot(self, slot: int, length: int) -> None:
        """Content-dedup the full pages an admission just finalized for
        ``slot`` (pages wholly below the write frontier ``length`` — the
        spans decode and speculative rollback can never touch).

        Each page this row *exclusively* owns is hashed; a digest match
        against the :class:`~repro.serve.cache.PageDedupIndex` is only a
        candidate — the share happens after a full byte compare confirms
        it (a hash collision is counted and degrades to a miss, so
        sharing is unconditionally bit-exact).  On a confirmed match the
        fresh page is dropped for a reference to the resident one;
        otherwise the fresh page is indexed for future admissions."""
        ps = self.page_size
        row = self.table[slot]
        shared_any = False
        for j in range(length // ps):
            p = int(row[j])
            if p == 0 or self.pool.refcount[p] != 1:
                # already shared (prefix trie, session snapshot, or an
                # earlier dedup hit) — nothing to save
                continue
            data = self._page_bytes_of(p)
            digest = self._digest_fn(data)
            match = None
            for c in self.dedup.candidates(digest):
                if c == p:
                    continue
                if self.pool.shard_of(c) != self.pool.shard_of(p):
                    # a cross-shard share would reference another block's
                    # page from this shard's table — never allowed
                    continue
                if self._page_bytes_of(c) == data:
                    match = c
                    break
                self.stats["dedup_hash_collisions"] += 1
            if match is None:
                self.dedup.insert(p, digest)
            else:
                self.pool.ref(match)
                row[j] = match
                self._deref_row_pages(np.asarray([p]))   # frees the copy
                self.stats["dedup_pages_shared"] += 1
                shared_any = True
        if shared_any:
            self.stats["dedup_hits"] += 1

    # ------------------------------------------------------------ admit
    def _effective_chunk(self) -> int:
        """The prefill chunk cap for admissions planned right now: the
        configured ``prefill_chunk``, stepped down to the smallest shape
        bucket while the degrade ladder holds level ``SMALL_CHUNKS`` or
        above (already-compiled buckets, so degrading never compiles)."""
        if self.ladder is not None and \
                self.ladder.level >= DegradeLadder.SMALL_CHUNKS:
            return self.chunk_buckets[0]
        return self.prefill_chunk

    def _feed_cost_model(self, chunk_s: Optional[float] = None,
                         step_s: Optional[float] = None,
                         tokens_per_step: Optional[float] = None) -> None:
        """EWMA the newest measured prefill-chunk / decode-step time (and
        decode tokens-per-step rate — the speculative multiplier the SLO
        math must price) into the scheduler's cost model."""
        if chunk_s is not None:
            self._chunk_ewma = (chunk_s if self._chunk_ewma is None else
                                (1 - _COST_EWMA) * self._chunk_ewma
                                + _COST_EWMA * chunk_s)
        if step_s is not None:
            self._step_ewma = (step_s if self._step_ewma is None else
                               (1 - _COST_EWMA) * self._step_ewma
                               + _COST_EWMA * step_s)
        if tokens_per_step is not None:
            self._tps_ewma = (tokens_per_step if self._tps_ewma is None else
                              (1 - _COST_EWMA) * self._tps_ewma
                              + _COST_EWMA * tokens_per_step)
        self.scheduler.update_cost_model(self._chunk_ewma, self._step_ewma,
                                         self._tps_ewma)

    def _admit(self, slot: int, req: Request) -> List[Request]:
        """Admit ``req`` into ``slot``: prefix-cache lookup, then zero-copy
        page sharing + boundary copy-on-write (paged) or page copy / slot
        reset (contiguous), then chunked prefill of the (remaining)
        context; samples the request's first token from the prefill
        logits.  A paged admission that finds the pool exhausted — even
        after reclaiming LRU retired entries — is *deferred*: re-queued at
        the head of the pending queue, never dropped."""
        if req.admit_t is None:
            req.admit_t = self.scheduler.clock()
        with self._phase("serve.admit.plan", "admit_plan_s"):
            sp = req.sampling or GREEDY
            ctx = req.context
            slot32 = jnp.asarray(slot, jnp.int32)
            sh = self._slot_shard(slot)

            # ---- prefix-cache lookup: reuse the longest resident prefix
            # (mesh-sharded: only same-shard matches — page sharing can never
            # cross a shard boundary, the pages live in different pool blocks)
            reuse, src, removed = 0, -1, False
            if self.prefix is not None:
                allowed = (None if self.mesh_plan is None
                           else (lambda s: self._slot_shard(s) == sh))
                match_len, match_slot = self.prefix.longest_match(
                    ctx, allowed=allowed)
                match_len = min(match_len, len(ctx) - 1)   # keep >= 1 token to
                if match_len >= self.min_prefix:           # prefill for logits
                    reuse, src = match_len, match_slot
                # the slot's pages are about to be overwritten: its old entry
                # must stop matching NOW (later admissions in this same step
                # would otherwise copy half-overwritten pages)
                removed = self.prefix.remove(slot)

            # ---- session snapshot: a returning conversation's accumulated
            # history re-admits as shared pages even after every slot turned
            # over (the trie only sees *resident* rows) — used when it covers
            # more than the best trie match
            sess_row = None
            conv = getattr(req, "_conv_id", None)
            if self.paged and conv is not None:
                sess = self.sessions.get(conv)
                if sess is not None and sess.row is not None:
                    s_reuse = min(sess.covered, len(ctx) - 1)
                    if self.mesh_plan is not None and \
                            self.pool.shard_of(int(sess.row[0])) != sh:
                        # the snapshot's pages live in another shard's block;
                        # this admission must re-prefill (or use the trie)
                        s_reuse = 0
                    if s_reuse >= self.min_prefix and s_reuse > reuse:
                        reuse, src = s_reuse, -1
                        sess_row = sess.row

            # ---- plan the prefill pieces over the remaining context
            # (the degrade ladder caps the chunk under overload)
            chunk = self._effective_chunk()
            pieces = []
            pos = reuse
            prefill_end = reuse
            while pos < len(ctx):
                piece = ctx[pos:pos + chunk]
                cb = next(b for b in self.chunk_buckets if b >= len(piece))
                # bucket padding writes (masked-off) cache positions
                # [pos, pos+cb); past max_seq dynamic_update_slice would CLAMP
                # the start and silently overwrite valid earlier positions.
                # Shrink the tail bucket to the cache room instead (one extra
                # compile per distinct tail size, only for near-capacity
                # prompts).
                cb = min(cb, self.max_seq - pos)
                toks = np.zeros((1, cb), np.int32)
                toks[0, :len(piece)] = piece
                pieces.append((pos, len(piece), cb, self._put_rep(toks)))
                prefill_end = max(prefill_end, pos + cb)
                pos += len(piece)

            # ---- bind physical pages (paged) — may defer on pool exhaustion
            cow = None
            if self.paged:
                in_place = bool(reuse) and sess_row is None and src == slot
                row_src = sess_row if sess_row is not None else (
                    self.table[src] if reuse and not in_place else None)
                ok, cow = self._bind_pages(slot, row_src, reuse, prefill_end,
                                           in_place=in_place)
                if not ok:
                    if removed and src != slot:    # the entry is gone even
                        self.stats["prefix_evictions"] += 1   # on deferral
                    self.stats["oom_deferred"] += 1
                    self.scheduler.evict(slot)     # head of queue: deferred,
                    if not self.scheduler.active and not self.pool.used_count:
                        n_pages = self.pool.num_pages - self.pool.shards
                        raise RuntimeError(        # not dropped
                            f"page pool ({n_pages} pages of "
                            f"{self.page_size} tokens"
                            + (f", {self.pool.shards} shard blocks"
                               if self.pool.shards > 1 else "")
                            + f") cannot hold a single request of "
                            f"{len(ctx)} context tokens")
                    return []

            # ---- admission committed: account the lookup + bytes moved
            # (session-sourced reuse is tallied separately — the trie
            # counters keep meaning "the trie found/missed it")
            if sess_row is not None:
                self.stats["session_hits"] += 1
                self.stats["session_reused_tokens"] += reuse
            if self.prefix is not None:
                if reuse and sess_row is None:
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_reused_tokens"] += reuse
                elif not reuse:
                    self.stats["prefix_misses"] += 1
                if removed and src != slot:
                    self.stats["prefix_evictions"] += 1

            row = None
            if self.paged:
                if self.mesh_plan is None:
                    row = jnp.asarray(self.table[slot])
                else:
                    # (shards, max_pages) lane-sharded dispatch rows: the
                    # target shard gets the slot's localized row, every other
                    # shard an all-scratch row — their prefill runs on
                    # garbage the host discards, the target lane is
                    # bit-exact
                    rows = np.zeros((self.shards, self.max_pages), np.int32)
                    rows[sh] = self.mesh_plan.local_pages(self.table[slot])
                    row = self._put_lane(rows)
            for start, nvalid, cb, toks in pieces:
                self._ensure_warm(("prefill", cb), self._prefill_exe(cb),
                                  self.params, self.state, toks,
                                  row if self.paged else slot32,
                                  self._put_rep(jnp.asarray(start, jnp.int32)),
                                  self._put_rep(
                                      jnp.asarray(nvalid, jnp.int32)),
                                  self._put_rep(jnp.asarray(0.0, jnp.float32)),
                                  self._put_rep(jnp.asarray(0, jnp.int32)),
                                  self._put_rep(jnp.asarray(1.0, jnp.float32)),
                                  self._put_rep(jnp.asarray(0, jnp.int32)),
                                  self._put_rep(jnp.asarray(0, jnp.int32)))
            if self.paged:
                if cow is not None:
                    page_copy = self._page_copy_exe()
                    if self.mesh_plan is None:
                        self._ensure_warm("page_copy", page_copy, self.state,
                                          slot32, slot32)
                    else:
                        lane0 = self._put_lane(np.zeros(self.shards, np.int32))
                        self._ensure_warm("page_copy", page_copy, self.state,
                                          lane0, lane0)
            else:
                reset = self._reset_exe()
                self._ensure_warm("reset", reset, self.state, slot32)
                if reuse and src != slot:
                    copy = self._copy_exe()
                    self._ensure_warm("copy", copy, self.state, slot32, slot32)
            # the first prefill token continues the request's sample stream
            temp = self._put_rep(jnp.asarray(sp.temperature, jnp.float32))
            top_k = self._put_rep(jnp.asarray(sp.top_k, jnp.int32))
            top_p = self._put_rep(jnp.asarray(sp.top_p, jnp.float32))
            seed = self._put_rep(jnp.asarray(sp.seed, jnp.int32))
            sidx = self._put_rep(jnp.asarray(len(req.generated), jnp.int32))

        with self._phase("serve.admit.prefill"):
            t0 = time.perf_counter()
            if self.paged:
                if cow is not None:
                    # copy-on-write: ONE boundary page, not the whole prefix
                    if self.mesh_plan is None:
                        cow_args = (jnp.asarray(cow[0], jnp.int32),
                                    jnp.asarray(cow[1], jnp.int32))
                    else:
                        # per-shard src/dst lanes of shard-local ids: only the
                        # target shard copies, the rest self-copy scratch
                        blk = self.mesh_plan.block
                        src_v = np.zeros(self.shards, np.int32)
                        dst_v = np.zeros(self.shards, np.int32)
                        src_v[sh] = cow[0] % blk
                        dst_v[sh] = cow[1] % blk
                        cow_args = (self._put_lane(src_v),
                                    self._put_lane(dst_v))
                    self.state = page_copy(self.state, *cow_args)
                    self.stats["prefix_bytes_copied"] += self.page_bytes
                    self.stats["pages_cow"] += 1
            elif reuse and src != slot:
                self.state = copy(self.state, jnp.asarray(src, jnp.int32),
                                  slot32)
                self.stats["prefix_bytes_copied"] += self.slot_bytes
            elif not reuse:
                self.state = reset(self.state, slot32)
            # (contiguous reuse with src == slot: the pages are already there;
            #  paged cold / shared-full-pages: zero bytes move at admission)
            nxt = None
            for start, nvalid, cb, toks in pieces:
                nxt, _, self.state = self._prefill_exe(cb)(
                    self.params, self.state, toks,
                    row if self.paged else slot32,
                    self._put_rep(jnp.asarray(start, jnp.int32)),
                    self._put_rep(jnp.asarray(nvalid, jnp.int32)),
                    temp, top_k, top_p, seed, sidx)
            nxt.block_until_ready()
            dt = time.perf_counter() - t0
        with self._phase("serve.admit.commit", "admit_commit_s"):
            self.stats["prefill_s"] += dt
            self.stats["prefill_tokens"] += len(ctx) - reuse
            # row i of the prefilled span [reuse, len(ctx)) attends i + 1
            self.stats["prefill_context_tokens"] += (
                (reuse + 1 + len(ctx)) * (len(ctx) - reuse) // 2)
            self.stats["prefill_dispatches"] += len(pieces)
            self._count_sample([sp], len(pieces))
            self.stats["admissions"] += 1
            if self.prefix is not None:
                self.stats["hit_admit_s" if reuse else "cold_admit_s"] += dt
                self._admit_times["hit" if reuse else "cold"].append(dt)
            if self.paged:
                # restore the all-zeros scratch invariant this admission's
                # prefill broadcasts dirtied, BEFORE the next admission or
                # decode reads scratch through masked lanes
                self._scrub_scratch()
            if not reuse:
                # prefix-hit admissions time a page copy plus (at most) a
                # tiny tail chunk — feeding that into the model would make a
                # "chunk" look far cheaper than a full prefill dispatch;
                # only cold admissions give an unbiased chunk cost
                self._feed_cost_model(chunk_s=dt / max(1, len(pieces)))
            # sharded prefill returns one sampled lane per shard — only the
            # target shard's is real (sh == 0 single-device, where nxt is
            # (1,)); a request's first token is held until step() returns
            first = req.first_token_t is None
            self.scheduler.on_prefill(req, int(np.asarray(nxt)[sh]))
            if first:
                self._first_reads.append(time.perf_counter())
            if self.drafter is not None:
                # fresh speculative bookkeeping for the slot's new occupant:
                # exactly one unmaterialized token (the first sample above), a
                # cold suffix cache, and no accept/head history to inherit
                self._spec_unwritten[slot] = 1
                self._suffix_caches[slot] = self.drafter.make_cache()
                self._head_preds.pop(slot, None)
                self._slot_accept.pop(slot, None)
                self._shape_age.pop(slot, None)
                self._slot_tps.pop(slot, None)
            if self.prefix is not None:
                # the slot's pages now hold exactly ctx (the sampled first
                # token is not written until the next decode step feeds it)
                evicted = self.prefix.insert(slot, ctx)
                if self.paged:
                    self._release_trie_evicted(evicted)
            if self.dedup is not None:
                # content-dedup the full pages this admission finalized: any
                # byte-identical resident page — wherever it sits in either
                # sequence — replaces this row's fresh copy by reference
                self._dedup_slot(slot, len(ctx))
            if req.slot is None:                   # retired on its first token
                self._session_retire(req, slot)
                if self.paged and not self._row_reusable(slot):
                    self._release_row(slot)
                return [req]
            return []

    # ------------------------------------------------------------- step
    def _decode_once(self) -> List[Request]:
        """One batched decode step over every live slot (idle slots run the
        greedy lane and their outputs are discarded)."""
        with self._phase("serve.decode.prepare", "decode_prepare_s"):
            pages_extra = ()
            if self.paged:
                # lazily allocate each live slot's write page for this step; a
                # slot that cannot get one even after reclaim is preempted
                # back to the queue (deferred, not dropped)
                for slot, req in list(self.scheduler.active.items()):
                    if not self._ensure_pages(slot, req.pos, req.pos + 1):
                        self.evict(slot)
                        self.stats["oom_deferred"] += 1
                if not self.scheduler.active:
                    return []
            tokens = np.zeros((self.max_slots, 1), np.int32)
            positions = np.zeros((self.max_slots,), np.int32)
            sps = [GREEDY] * self.max_slots
            sidx = [0] * self.max_slots
            for slot, req in self.scheduler.active.items():
                tokens[slot, 0] = req.generated[-1]
                positions[slot] = req.pos
                sps[slot] = req.sampling or GREEDY
                sidx[slot] = len(req.generated)
            if self.paged:
                # idle lanes point their whole page-table row at the scratch
                # page: their unconditional (discarded) writes can never touch
                # a retired-but-reusable slot's real pages
                disp = np.zeros((self.max_slots, self.max_pages), np.int32)
                for slot in self.scheduler.active:
                    disp[slot] = self.table[slot]
                pages_extra = (self._put_lane(self._local_disp(disp)),)
            elif self.prefix is not None:
                # idle lanes run in the shared dispatch too, and their
                # (discarded) token's KV is written unconditionally at
                # positions[slot]; aim each idle write at the first cache
                # position the trie does NOT index, so a retired slot's
                # matchable prefix survives until the slot is actually reused
                for slot in range(self.max_slots):
                    if slot in self.scheduler.active:
                        continue
                    n = self.prefix.length(slot)
                    if n is None:
                        continue
                    if n >= self.max_seq:   # pages full: no safe position left
                        self.prefix.remove(slot)
                        self.stats["prefix_evictions"] += 1
                    else:
                        positions[slot] = n
            temps, top_ks, top_ps, seeds, idxs = (
                self._put_lane(a) for a in sampling_lanes(sps, sidx))
            toks_d = self._put_lane(tokens)
            pos_d = self._put_lane(positions)
            exe = self._decode_exe()
            self._ensure_warm("decode", exe, self.params, self.state,
                              toks_d, pos_d, *pages_extra,
                              temps, top_ks, top_ps, seeds, idxs)
            occ = self.scheduler.occupancy
            live = list(self.scheduler.active)

        with self._phase("serve.decode.device"):
            t0 = time.perf_counter()
            nxt, lg, self.state = exe(self.params, self.state, toks_d, pos_d,
                                      *pages_extra,
                                      temps, top_ks, top_ps, seeds, idxs)
            nxt = np.asarray(nxt)
            dt = time.perf_counter() - t0
        with self._phase("serve.decode.commit", "decode_commit_s"):
            if self.trace_logits:
                self.logit_trace.append(np.asarray(lg)[live])
            self.stats["decode_s"] += dt
            self.stats["decode_tokens"] += len(live)
            self.stats["decode_steps"] += 1
            self._count_sample(sps)
            self.stats["decode_lane_steps"] += len(live)
            self.stats["decode_context_tokens"] += (
                int(positions[live].sum()) + len(live))
            self.stats["occupancy_sum"] += occ
            for slot in live:
                self._shard_lane_steps[self._slot_shard(slot)] += 1
            self._step_times.append(dt)
            self._feed_cost_model(step_s=dt, tokens_per_step=1.0)
            if self.prefix is not None:
                # this step wrote each live slot's fed token into its pages
                for slot in live:
                    self.prefix.extend(slot, int(tokens[slot, 0]))
            reqs = {s: self.scheduler.active[s] for s in live}
            done = self.scheduler.on_decode({s: int(nxt[s]) for s in live})
            for slot in live:
                if slot not in self.scheduler.active:
                    # retiring session turns snapshot their page row (one
                    # pool ref per page) before any release can free it
                    self._session_retire(reqs[slot], slot)
            if self.paged:
                # free a retiring slot's pages the moment nothing can reuse
                # them: no prefix cache at all, or its trie entry was LRU-
                # evicted while the slot was live (keeping the row would
                # strand it — reclaim only scans trie entries)
                for slot in live:
                    if slot not in self.scheduler.active and \
                            not self._row_reusable(slot):
                        self._release_row(slot)
            return done

    # ----------------------------------------------- speculative decode
    def _truncate_emitted(self, req: Request, emitted: List[int]
                          ) -> List[int]:
        """Clip a slot's emitted tokens at its retire point: sequential
        decode would never sample past ``eos_id`` or the ``max_new``
        budget, so speculative output must stop at the same token."""
        out: List[int] = []
        room = req.remaining
        for t in emitted:
            if room <= 0:
                break
            out.append(t)
            room -= 1
            if req.eos_id is not None and t == req.eos_id:
                break
        return out

    def _update_slot_accept(self, slot: int, shape: str, successes: int,
                            trials: int, mean_branch: float) -> None:
        """Fold one step's acceptance outcome into ``slot``'s per-candidate
        accept-rate EWMA for ``shape`` (``mean_branch`` > 1 inverts a tree
        step's per-level rate back to per-candidate — see
        :func:`repro.serve.spec.per_candidate_accept`).  Shapes are
        estimated separately because they may draft through different
        predictors (n-gram lookup vs trained draft heads); folding both
        into one rate made the auto reconfigurator oscillate whenever the
        drafters' hit rates diverged."""
        if trials <= 0:
            return
        p = per_candidate_accept(successes, trials, mean_branch)
        per = self._slot_accept.setdefault(slot, {})
        # blend the FIRST observation with the prior too: a single failed
        # opening step must not write an irrecoverable 0.0 — at rate 0 a
        # shape is never picked again, so its estimate would never heal
        prev = per.get(shape, _ACCEPT_PRIOR)
        per[shape] = (1 - _ACCEPT_EWMA) * prev + _ACCEPT_EWMA * p

    def _feed_slot_rate(self, slot: int, rate: float) -> None:
        """EWMA ``slot``'s expected emitted-tokens-per-step into the
        scheduler's per-slot cost model (the Lemma-3 closed form priced
        from the slot's own accept estimate, not the batch mean)."""
        prev = self._slot_tps.get(slot)
        r = (rate if prev is None
             else (1 - _COST_EWMA) * prev + _COST_EWMA * rate)
        self._slot_tps[slot] = r
        self.scheduler.slot_tokens_per_step[slot] = max(1.0, r)

    def _spec_decode_once(self) -> List[Request]:
        """One speculative decode step over every live slot: draft up to
        ``spec_k`` tokens per slot on the host (prompt lookup over its own
        history, served from the slot's incremental suffix cache), verify
        all K+1 positions in ONE dispatch, emit each slot's longest
        sampled-matching draft prefix plus one correction/bonus token,
        then rewind per-slot lengths and release any page advanced past
        the accepted point.  Idle lanes run with ``nspec == 0`` — every
        one of their cache writes is masked off."""
        with self._phase("serve.decode.prepare", "decode_prepare_s"):
            k = self.spec_k
            drafts: Dict[int, List[int]] = {}
            for slot, req in self.scheduler.active.items():
                # a draft past the cache capacity or the generation budget
                # could never be emitted — don't verify (or page) it
                kd = min(k, self.max_seq - req.pos - 1, req.remaining - 1)
                sc = self._suffix_caches.get(slot)
                if kd <= 0:
                    drafts[slot] = []
                elif sc is not None:
                    drafts[slot] = self.drafter.propose_cached(
                        sc, req.context, kd)
                else:
                    drafts[slot] = self.drafter.propose(req.context, kd)
            if self.paged:
                for slot, req in list(self.scheduler.active.items()):
                    end = req.pos + 1 + len(drafts[slot])
                    if not self._ensure_pages(slot, req.pos, end):
                        # not even the draft-free step fits: defer, not drop
                        drafts[slot] = []
                        if not self._ensure_pages(slot, req.pos, req.pos + 1):
                            self.evict(slot)
                            self.stats["oom_deferred"] += 1
                if not self.scheduler.active:
                    return []
            b = self.max_slots
            tokens = np.zeros((b, k + 1), np.int32)
            positions = np.zeros((b,), np.int32)
            nspec = np.zeros((b,), np.int32)     # idle lanes: writes masked
            sps = [GREEDY] * b
            sidx = [0] * b
            for slot, req in self.scheduler.active.items():
                d = drafts[slot]
                tokens[slot, 0] = req.generated[-1]
                if d:
                    tokens[slot, 1:1 + len(d)] = d
                positions[slot] = req.pos
                nspec[slot] = 1 + len(d)
                sps[slot] = req.sampling or GREEDY
                sidx[slot] = len(req.generated)
            pages_extra = ()
            if self.paged:
                disp = np.zeros((b, self.max_pages), np.int32)
                for slot in self.scheduler.active:
                    disp[slot] = self.table[slot]
                pages_extra = (self._put_lane(self._local_disp(disp)),)
            temps, top_ks, top_ps, seeds, idxs = (
                self._put_lane(a) for a in sampling_lanes(sps, sidx))
            toks_d = self._put_lane(tokens)
            pos_d = self._put_lane(positions)
            nspec_d = self._put_lane(nspec)
            exe = self._spec_exe()
            self._ensure_warm("spec", exe, self.params, self.state, toks_d,
                              pos_d, *pages_extra, nspec_d, temps, top_ks,
                              top_ps, seeds, idxs)
            occ = self.scheduler.occupancy
            live = list(self.scheduler.active)

        with self._phase("serve.decode.device"):
            t0 = time.perf_counter()
            nxt, lg, self.state = exe(self.params, self.state, toks_d, pos_d,
                                      *pages_extra, nspec_d, temps, top_ks,
                                      top_ps, seeds, idxs)
            nxt = np.asarray(nxt)
            dt = time.perf_counter() - t0
        with self._phase("serve.decode.commit", "decode_commit_s"):
            if self.trace_logits:
                self.logit_trace.append(np.asarray(lg)[live])

            emitted: Dict[int, List[int]] = {}
            n_emitted = 0
            for slot in live:
                req = self.scheduler.active[slot]
                d = drafts[slot]
                toks, accepted = accept_tokens(nxt[slot], d)
                toks = self._truncate_emitted(req, toks)
                emitted[slot] = toks
                n_emitted += len(toks)
                self.stats["spec_drafted"] += len(d)
                self.stats["spec_accepted"] += accepted
                if d:
                    self.stats["spec_lanes_drafted"] += 1
                    if accepted:
                        self.stats["spec_lanes_hit"] += 1
                    # candidates tested: the accepted prefix plus the first
                    # mismatch (if the walk stopped inside the draft)
                    self._update_slot_accept(
                        slot, "chain", accepted,
                        accepted + (1 if accepted < len(d) else 0), 1.0)
                p = self._slot_accept.get(slot, {}).get("chain")
                if p is not None:
                    self._feed_slot_rate(slot, expected_tokens_chain(p, k))
            self.stats["decode_s"] += dt
            self.stats["decode_tokens"] += n_emitted
            self.stats["decode_steps"] += 1
            self._count_sample(sps)
            self.stats["spec_steps"] += 1
            self.stats["decode_lane_steps"] += len(live)
            self.stats["decode_context_tokens"] += (
                int(positions[live].sum()) + len(live))
            self.stats["occupancy_sum"] += occ
            for slot in live:
                self._shard_lane_steps[self._slot_shard(slot)] += 1
            self._step_times.append(dt)
            self._feed_cost_model(step_s=dt,
                                  tokens_per_step=n_emitted / len(live))
            if self.prefix is not None:
                # the step materialized each slot's fed-and-kept tokens: the
                # last sampled token plus its accepted draft prefix
                for slot in live:
                    fed = ([int(tokens[slot, 0])]
                           + drafts[slot][:len(emitted[slot]) - 1])
                    for t in fed:
                        self.prefix.extend(slot, t)
            new_len = {slot: int(positions[slot]) + len(emitted[slot])
                       for slot in live}
            reqs = {s: self.scheduler.active[s] for s in live}
            done = self.scheduler.on_decode_tokens(emitted)
            if self.paged:
                for slot in live:
                    # rewind: rejected-draft pages past the accepted frontier
                    self._rollback_pages(slot, new_len[slot])
                    if slot not in self.scheduler.active:
                        # snapshot AFTER rollback: the row maps exactly the
                        # accepted (materialized) positions
                        self._session_retire(reqs[slot], slot)
                        if not self._row_reusable(slot):
                            self._release_row(slot)
            else:
                for slot in live:
                    if slot not in self.scheduler.active:
                        self._session_retire(reqs[slot], slot)
            return done

    def _tree_decode_once(self, draft: bool = True) -> List[Request]:
        """One tree-speculative decode step over every live slot.

        Per slot the fed (B, C) block is its **chain part** — the
        ``_spec_unwritten`` emitted tokens the previous step accepted but
        did not materialize, committed through the page table at
        ``[index, index + u)`` — followed by its drafted **tree part**,
        whose KV lands only in the attended view (pool scatter redirects
        drafted rows to the scratch page, so a rejected branch conserves
        refcounts with no rollback at all).  Acceptance walks the longest
        sampled-matching root-to-leaf path; the accepted tokens become the
        NEXT step's chain part.  Chain speculative decode is exactly the
        degenerate case ``u == 1`` with a single-path tree; ``draft=False``
        (the degrade ladder's SPEC_OFF level) still runs this dispatch with
        zero drafted nodes, draining the chain part it must commit.

        In ``spec_mode="auto"`` each slot's accept-rate EWMA prices the
        Lemma-3 closed forms and picks a chain-``spec_k`` or
        tree-``(spec_branch, d)`` draft shape per step — both run inside
        the same compiled wide dispatch, so the reconfiguration is free."""
        with self._phase("serve.decode.prepare", "decode_prepare_s"):
            branch = self.spec_branch
            heads = self.head_drafter
            cw = self._tree_width()
            trees: Dict[int, Optional[TreeDraft]] = {}
            shapes: Dict[int, str] = {}
            u_map: Dict[int, int] = {}
            if self.paged:
                # the chain part is already emitted — it cannot shrink, so a
                # slot that cannot page it is evicted (deferred, not dropped);
                # drafted rows need no pages (they only ever touch scratch)
                for slot, req in list(self.scheduler.active.items()):
                    u = self._spec_unwritten.get(slot, 1)
                    index = req.pos + 1 - u
                    if not self._ensure_pages(slot, index, index + u):
                        self.evict(slot)
                        self._spec_unwritten.pop(slot, None)
                        self.stats["oom_deferred"] += 1
                if not self.scheduler.active:
                    return []

            # ---- drafting + the per-slot reconfigurator decision
            for slot, req in self.scheduler.active.items():
                u = self._spec_unwritten.get(slot, 1)
                u_map[slot] = u
                index = req.pos + 1 - u
                room = self.max_seq - index - u   # cache rows left for drafts
                max_depth = min(req.remaining - 1, room)
                nodes = min(self.spec_tree_nodes, room, cw - u)
                tree: Optional[TreeDraft] = None
                if draft and nodes > 0 and max_depth > 0:
                    acc = self._slot_accept.get(slot, {})
                    p_chain = acc.get("chain", _ACCEPT_PRIOR)
                    p_tree = acc.get("tree", _ACCEPT_PRIOR)
                    shape = "tree"
                    kd = min(self.spec_k, room, max_depth, cw - u)
                    if self.spec_mode == "auto":
                        # both shapes run in the same wide dispatch: equal
                        # step cost, so the decision is purely on expected
                        # emitted tokens (Lemma 3's crossover), each shape
                        # priced at its own drafter's accept estimate
                        shape = pick_shape(p_chain, p_tree, kd, nodes, branch)
                        other = "tree" if shape == "chain" else "chain"
                        age = self._shape_age.setdefault(
                            slot, {"chain": 0, "tree": 0})
                        explore = age[other] >= _EXPLORE_EVERY
                        if explore:
                            shape = other
                        age[shape] = 0
                        age["tree" if shape == "chain" else "chain"] += 1
                        self.stats[f"spec_shape_{shape}"] += 1
                        rec = {"slot": slot, "accept_chain": round(p_chain, 4),
                               "accept_tree": round(p_tree, 4), "shape": shape}
                        if explore:
                            rec["explore"] = True
                        self._spec_decisions.append(rec)
                    sc = self._suffix_caches.get(slot)
                    if shape == "chain":
                        d = (self.drafter.propose_cached(sc, req.context, kd)
                             if sc is not None
                             else self.drafter.propose(req.context, kd))
                        tree = TreeDraft.chain(tuple(d)) if d else None
                    elif heads is not None and slot in self._head_preds:
                        tree = heads.propose_tree(self._head_preds[slot],
                                                  nodes, branch, max_depth)
                    elif sc is not None:
                        # cap the drafted depth so the budget buys hedges: a
                        # branch-wide fan per spine level costs `branch`
                        # nodes/level (uncapped, the rank-0 spine would eat
                        # the whole budget and the "tree" degenerates to a
                        # chain) — the same nodes//branch shape the Lemma-3
                        # expected-tokens model prices
                        tree = self.tree_drafter.propose_tree(
                            sc, req.context, nodes, branch,
                            min(max_depth, max(1, nodes // branch)))
                    if tree is not None and tree.n == 0:
                        tree = None
                    shapes[slot] = shape
                trees[slot] = tree

            # ---- assemble the (B, C) block
            b = self.max_slots
            tokens = np.zeros((b, cw), np.int32)
            # padding rows parent themselves: never an ancestor of a valid row
            parents = np.broadcast_to(np.arange(cw, dtype=np.int32),
                                      (b, cw)).copy()
            pos_off = np.zeros((b, cw), np.int32)
            positions = np.zeros((b,), np.int32)
            nchain = np.zeros((b,), np.int32)   # idle lanes: 0, writes masked
            nspec = np.zeros((b,), np.int32)
            sps = [GREEDY] * b
            sidx = [0] * b
            for slot, req in self.scheduler.active.items():
                u = u_map[slot]
                ctx = req.context
                tokens[slot, :u] = ctx[len(ctx) - u:]
                parents[slot, 0] = -1
                if u > 1:
                    parents[slot, 1:u] = np.arange(u - 1, dtype=np.int32)
                pos_off[slot, :u] = np.arange(u, dtype=np.int32)
                tree = trees[slot]
                n = tree.n if tree is not None else 0
                if n:
                    tokens[slot, u:u + n] = tree.tokens
                    parents[slot, u:u + n] = [u - 1 if p < 0 else u + p
                                              for p in tree.parents]
                    pos_off[slot, u:u + n] = [u - 1 + d for d in tree.depths]
                positions[slot] = req.pos + 1 - u
                nchain[slot] = u
                nspec[slot] = u + n
                sps[slot] = req.sampling or GREEDY
                sidx[slot] = len(req.generated)
            pages_extra = ()
            if self.paged:
                disp = np.zeros((b, self.max_pages), np.int32)
                for slot in self.scheduler.active:
                    disp[slot] = self.table[slot]
                pages_extra = (self._put_lane(self._local_disp(disp)),)
            temps, top_ks, top_ps, seeds, idxs = (
                self._put_lane(a) for a in sampling_lanes(sps, sidx))
            toks_d = self._put_lane(tokens)
            pos_d = self._put_lane(positions)
            par_d = self._put_lane(parents)
            off_d = self._put_lane(pos_off)
            nch_d = self._put_lane(nchain)
            nsp_d = self._put_lane(nspec)
            exe = self._tree_exe()
            self._ensure_warm("tree", exe, self.params, self.state, toks_d,
                              pos_d, *pages_extra, par_d, off_d, nch_d, nsp_d,
                              temps, top_ks, top_ps, seeds, idxs)
            occ = self.scheduler.occupancy
            live = list(self.scheduler.active)

        with self._phase("serve.decode.device"):
            t0 = time.perf_counter()
            nxt, head_top, lg, self.state = exe(
                self.params, self.state, toks_d, pos_d, *pages_extra, par_d,
                off_d, nch_d, nsp_d, temps, top_ks, top_ps, seeds, idxs)
            nxt = np.asarray(nxt)
            dt = time.perf_counter() - t0
        with self._phase("serve.decode.commit", "decode_commit_s"):
            if self.trace_logits:
                self.logit_trace.append(np.asarray(lg)[live])
            head_np = np.asarray(head_top) if heads is not None else None

            # ---- longest accepted root-to-leaf path per slot
            emitted: Dict[int, List[int]] = {}
            n_emitted = 0
            for slot in live:
                req = self.scheduler.active[slot]
                u = u_map[slot]
                tree = trees[slot]
                if tree is not None:
                    sampled = [int(nxt[slot, u - 1])] + [
                        int(nxt[slot, u + i]) for i in range(tree.n)]
                    toks, path = accept_path(sampled, tree)
                else:
                    toks, path = [int(nxt[slot, u - 1])], []
                toks = self._truncate_emitted(req, toks)
                emitted[slot] = toks
                n_emitted += len(toks)
                n = tree.n if tree is not None else 0
                self.stats["spec_drafted"] += n
                self.stats["spec_accepted"] += len(path)
                if n:
                    self.stats["spec_lanes_drafted"] += 1
                    if path:
                        self.stats["spec_lanes_hit"] += 1
                    # fold this step's outcome into the slot's accept EWMA:
                    # per accepted level the walk tested |children| candidates
                    # (plus the final failed level, if it had any to test)
                    kids: Dict[int, int] = {}
                    for par in tree.parents:
                        kids[par] = kids.get(par, 0) + 1
                    levels = []
                    cur = -1
                    for node in path:
                        levels.append(kids.get(cur, 0))
                        cur = node
                    fail = 1 if kids.get(cur, 0) else 0
                    if fail:
                        levels.append(kids[cur])
                    if levels:
                        self._update_slot_accept(
                            slot, shapes.get(slot, "tree"), len(path),
                            len(path) + fail, sum(levels) / len(levels))
                acc = self._slot_accept.get(slot, {})
                rates = []
                if "tree" in acc:
                    rates.append(expected_tokens_tree(
                        acc["tree"], self.spec_tree_nodes, branch))
                if "chain" in acc and self.spec_mode == "auto":
                    rates.append(expected_tokens_chain(acc["chain"],
                                                       self.spec_k))
                if rates:
                    # auto mode runs whichever shape prices better next step,
                    # so the scheduler sees the better of the two estimates
                    self._feed_slot_rate(slot, max(rates))
                if head_np is not None:
                    # head candidates at the last ACCEPTED row seed the next
                    # step's tree (they predict the depths after its sample)
                    r_star = (u - 1 if len(toks) <= 1
                              else u + path[len(toks) - 2])
                    self._head_preds[slot] = head_np[slot, r_star]

            self.stats["decode_s"] += dt
            self.stats["decode_tokens"] += n_emitted
            self.stats["decode_steps"] += 1
            self._count_sample(sps)
            self.stats["spec_steps"] += 1
            self.stats["spec_tree_steps"] += 1
            self.stats["decode_lane_steps"] += len(live)
            # positions + nchain: each live lane's req.pos + 1
            self.stats["decode_context_tokens"] += int(
                (positions + nchain)[live].sum())
            self.stats["occupancy_sum"] += occ
            for slot in live:
                self._shard_lane_steps[self._slot_shard(slot)] += 1
            self._step_times.append(dt)
            self._feed_cost_model(step_s=dt,
                                  tokens_per_step=n_emitted / len(live))
            if self.prefix is not None:
                # this step materialized each live slot's chain part
                for slot in live:
                    for t in tokens[slot, :u_map[slot]]:
                        self.prefix.extend(slot, int(t))
            reqs = {s: self.scheduler.active[s] for s in live}
            done = self.scheduler.on_decode_tokens(emitted)
            for slot in live:
                # the accepted path is the next step's chain part; drafted
                # rows only ever touched scratch, so there is NO page rollback
                self._spec_unwritten[slot] = max(1, len(emitted[slot]))
                if slot not in self.scheduler.active:
                    self._session_retire(reqs[slot], slot)
                    self._head_preds.pop(slot, None)
                    if self.paged and not self._row_reusable(slot):
                        self._release_row(slot)
            return done

    def step(self) -> List[Request]:
        """One engine iteration: degrade-ladder observation (when
        ``degrade`` is on), SLO preemption check, refill free slots
        (chunked prefill per admission), then one batched decode step shared
        by ALL live slots — speculative multi-token decode when ``spec_k``
        is set, the classic sequential step otherwise. Returns the requests
        that finished during this iteration (including any the ladder shed
        — retired-with-reason, never silently dropped)."""
        finished: List[Request] = []
        self._first_reads = []
        with self._phase("serve.step"):
            if self.ladder is not None:
                level = self.ladder.observe(self.scheduler.slo_pressure())
                if level:
                    self.stats["degrade_steps"] += 1
                if level >= DegradeLadder.SHED:
                    finished += self.scheduler.shed_hopeless()
            victim = self.scheduler.maybe_preempt()
            if victim is not None:
                self.evict(victim)
                self.stats["preemptions"] += 1
            for slot, req in self.scheduler.admissions():
                with self._phase("serve.admit"):
                    finished += self._admit(slot, req)
            if self.scheduler.active:
                spec_on = self.spec_k and not (
                    self.ladder is not None
                    and self.ladder.level >= DegradeLadder.SPEC_OFF)
                with self._phase("serve.decode"):
                    if self.spec_mode != "chain":
                        # tree/auto modes ALWAYS step through the tree
                        # dispatch: under SPEC_OFF it runs with zero
                        # drafted nodes, which still drains each slot's
                        # unmaterialized chain part
                        finished += self._tree_decode_once(
                            draft=bool(spec_on))
                    else:
                        finished += (self._spec_decode_once() if spec_on
                                     else self._decode_once())
        # first tokens this step's admissions read back reach the caller
        # only now, after the decode step that followed them
        end = time.perf_counter()
        self.stats["first_token_hold_s"] += sum(
            end - t for t in self._first_reads)
        self.stats["first_tokens"] += len(self._first_reads)
        return finished

    # -------------------------------------------------------------- run
    def run(self, max_steps: Optional[int] = None) -> List[Request]:
        """Drain all submitted work; returns finished requests in
        completion order. ``max_steps`` bounds engine iterations."""
        finished: List[Request] = []
        steps = 0
        while self.scheduler.has_work:
            if max_steps is not None and steps >= max_steps:
                break
            finished += self.step()
            steps += 1
        return finished
