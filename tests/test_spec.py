"""Speculative multi-token decode tests: prompt-lookup drafting, the
exact-match acceptance rule, engine-level bit-exactness vs sequential
decode AND the per-token reference loop (GQA + MLA), rejection rollback
with page-refcount conservation, prefix reuse of rolled-back slots, the
tokens-per-step-aware scheduler cost model, and a randomized (hypothesis)
admit / prefix-hit / spec-rollback / evict / retire churn that must leave
``PagePool`` refcounts exactly conserved."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.registry import get_config
from repro.models.common import init_params
from repro.models.registry import get_api
from repro.serve import (PromptLookupDrafter, Request, SamplingParams,
                         Scheduler, ServeEngine, accept_tokens,
                         propose_draft)
from repro.launch.serve import generate

jax.config.update("jax_enable_x64", False)

SPEC_ARCHS = ["llama3.2-3b", "minicpm3-4b"]     # GQA + MLA families


def _cfg(arch_id="llama3.2-3b", **over):
    return get_config(arch_id).reduced(dtype=jnp.float32, **over)


def _params(cfg, seed=0):
    api = get_api(cfg)
    return api, init_params(api.param_specs(cfg), jax.random.key(seed))


def _serve(cfg, params, prompts, gens, **kw):
    eng = ServeEngine(cfg, params, **kw)
    if isinstance(gens, int):
        gens = [gens] * len(prompts)
    reqs = [eng.submit(list(p), g) for p, g in zip(prompts, gens)]
    eng.run()
    return eng, [r.generated for r in reqs]


# ---------------------------------------------------------------------------
# drafting + acceptance (pure host logic)
# ---------------------------------------------------------------------------

def test_propose_draft_matches_longest_ngram():
    # history ends in (7, 8); the earlier (7, 8) is followed by 9, 1, 2
    hist = [5, 7, 8, 9, 1, 2, 7, 8]
    assert propose_draft(hist, 3) == [9, 1, 2]
    # longer suffix match wins over a shorter, more recent one
    hist = [1, 2, 3, 4, 9, 2, 3, 1, 2, 3]
    assert propose_draft(hist, 1) == [4]


def test_propose_draft_iterates_through_cycles():
    # a period-3 cycle: one lookup reaches the history end after at most
    # 3 tokens, iteration keeps extending through the cycle
    hist = [4, 5, 6] * 4
    assert propose_draft(hist, 8) == [4, 5, 6, 4, 5, 6, 4, 5]


def test_propose_draft_degenerate_inputs():
    assert propose_draft([], 4) == []
    assert propose_draft([3], 4) == []          # nothing earlier to match
    assert propose_draft([1, 2, 3], 0) == []
    assert propose_draft([9, 9], 4) == [9, 9, 9, 9]   # 1-token cycle
    # no recurring n-gram at all -> empty draft, step degrades to 1 token
    assert propose_draft([1, 2, 3, 4, 5], 4) == []


def test_drafter_validation_and_window():
    with pytest.raises(ValueError):
        PromptLookupDrafter(ngram_max=0)
    with pytest.raises(ValueError):
        PromptLookupDrafter(ngram_max=2, ngram_min=3)
    d = PromptLookupDrafter(ngram_max=2)
    assert d.propose([4, 5, 6] * 3, 4) == [4, 5, 6, 4]


def test_accept_tokens_longest_matching_prefix():
    # all drafts confirmed: k accepted + the bonus token
    emitted, a = accept_tokens([7, 8, 9, 4], [7, 8, 9])
    assert emitted == [7, 8, 9, 4] and a == 3
    # first mismatch: the sampled correction replaces the draft
    emitted, a = accept_tokens([7, 5, 9, 4], [7, 8, 9])
    assert emitted == [7, 5] and a == 1
    # immediate mismatch degrades to the classic single token
    emitted, a = accept_tokens([3, 5, 9, 4], [7, 8, 9])
    assert emitted == [3] and a == 0
    # no drafts: one token, like a sequential step
    emitted, a = accept_tokens([3], [])
    assert emitted == [3] and a == 0


# ---------------------------------------------------------------------------
# engine equivalence: speculative == sequential == per-token reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch_id", SPEC_ARCHS)
def test_spec_tokens_bitexact_vs_sequential_and_reference(arch_id):
    """Greedy tokens from the speculative engine equal the sequential
    engine's AND the legacy per-token loop's, for GQA and MLA, under
    continuous batching with slot refill (acceptance criterion)."""
    cfg = _cfg(arch_id)
    api, params = _params(cfg)
    rng = np.random.default_rng(31)
    # repetitive prompts so drafts are actually accepted (and random ones
    # so rejection paths run too)
    pat = rng.integers(0, cfg.vocab, (5,)).tolist()
    prompts = [pat * 4, rng.integers(0, cfg.vocab, (13,)).tolist(),
               pat * 3 + [1], rng.integers(0, cfg.vocab, (8,)).tolist()]
    gens = [10, 8, 12, 9]
    kw = dict(max_slots=2, max_seq=48, prefill_chunk=8)
    seq_eng, seq_toks = _serve(cfg, params, prompts, gens, spec_k=0, **kw)
    spec_eng, spec_toks = _serve(cfg, params, prompts, gens, spec_k=3, **kw)
    assert spec_eng.spec_k == 3 and seq_eng.spec_k == 0
    assert spec_toks == seq_toks
    # the per-token reference loop agrees request by request
    for p, toks in zip(prompts, spec_toks):
        ids, _ = generate(cfg, params, np.asarray([p], np.int32), len(toks))
        assert toks == ids[0, len(p):].tolist()
    st = spec_eng.stats_summary()
    assert st["spec_drafted"] > 0
    assert st["tokens_per_step"] > 1.0          # some drafts were accepted
    assert st["decode_steps"] < sum(gens)       # strictly fewer dispatches


def test_spec_stochastic_streams_bitexact_vs_sequential():
    """Sampled (temperature > 0) lanes are ALSO bit-exact: every emitted
    token is the draw sequential decode would make at that sample index
    (exact-match acceptance == rejection sampling for a delta proposal)."""
    cfg = _cfg()
    api, params = _params(cfg)
    rng = np.random.default_rng(32)
    prompts = [rng.integers(0, cfg.vocab, (n,)).tolist()
               for n in (14, 9, 20, 11)]
    sps = [SamplingParams(temperature=0.8, top_k=20, seed=7),
           SamplingParams(temperature=1.2, top_p=0.9, seed=3),
           SamplingParams(),                    # greedy lane in the mix
           SamplingParams(temperature=0.5, seed=11)]
    outs = {}
    for sk in (0, 4):
        eng = ServeEngine(cfg, params, max_slots=2, max_seq=48,
                          prefill_chunk=8, spec_k=sk)
        reqs = [eng.submit(p, 12, sampling=s) for p, s in zip(prompts, sps)]
        eng.run()
        outs[sk] = [r.generated for r in reqs]
    assert outs[0] == outs[4]


def test_spec_eos_and_budget_truncation():
    """A drafted block whose accepted prefix crosses eos (or the max_new
    budget) emits exactly what sequential decode would have."""
    cfg = _cfg()
    api, params = _params(cfg)
    rng = np.random.default_rng(33)
    prompts = [rng.integers(0, cfg.vocab, (n,)).tolist()
               for n in (10, 7, 15, 12)]
    outs = {}
    for sk in (0, 3):
        eng = ServeEngine(cfg, params, max_slots=2, max_seq=40,
                          prefill_chunk=8, spec_k=sk)
        reqs = [eng.submit(p, 14, eos_id=int(3 + i * 7))
                for i, p in enumerate(prompts)]
        eng.run()
        outs[sk] = [r.generated for r in reqs]
    assert outs[0] == outs[3]


def test_spec_fills_cache_to_capacity_bitexact():
    """Near max_seq the drafted block hangs past the cache end; masked
    writes must drop (not clamp-shift) the overhanging rows.  Regression
    test for the paged view write: dynamic_update_slice clamping silently
    corrupted the last in-cache positions."""
    cfg = _cfg()
    api, params = _params(cfg)
    rng = np.random.default_rng(34)
    prompts = [rng.integers(0, cfg.vocab, (16,)).tolist() for _ in range(2)]
    kw = dict(max_slots=2, max_seq=32, prefill_chunk=8)
    gens = [16, 16]                              # decode to the last slot
    for paged in (True, False):
        _, seq_toks = _serve(cfg, params, prompts, gens, spec_k=0,
                             paged_kv=paged, **kw)
        _, spec_toks = _serve(cfg, params, prompts, gens, spec_k=5,
                              paged_kv=paged, **kw)
        assert spec_toks == seq_toks, paged


def test_spec_auto_off_for_ssm():
    """SSM state cannot be rewound position-wise: spec_k resolves to 0
    (mirror of the paged_kv auto gate) and serving still works."""
    cfg = _cfg("falcon-mamba-7b")
    api, params = _params(cfg)
    eng = ServeEngine(cfg, params, max_slots=2, max_seq=32,
                      prefill_chunk=8, spec_k=4)
    assert eng.spec_k == 0 and eng.drafter is None
    r = eng.submit(list(range(8)), 4)
    eng.run()
    assert len(r.generated) == 4
    with pytest.raises(ValueError):
        ServeEngine(cfg, params, max_seq=32, spec_k=-1)


# ---------------------------------------------------------------------------
# rollback: rejected pages released, rolled-back slots stay reusable
# ---------------------------------------------------------------------------

def _table_refcounts(eng):
    """Per-page count of table rows mapping it (the ground truth the
    pool's refcounts must equal)."""
    counts = np.zeros(eng.pool.num_pages, np.int64)
    for slot in range(eng.max_slots):
        for lp in range(eng.max_pages):
            p = int(eng.table[slot, lp])
            if p:
                counts[p] += 1
    return counts


def _assert_refcounts_conserved(eng):
    counts = _table_refcounts(eng)
    for p in range(1, eng.pool.num_pages):
        assert int(eng.pool.refcount[p]) == counts[p], p
    assert eng.pool.used_count == int((counts[1:] > 0).sum())
    assert int(eng.pool.refcount[0]) == 1       # scratch stays pinned
    free = [p for fl in eng.pool._free for p in fl]   # per-shard lists
    assert len(free) == eng.pool.free_count
    assert all(int(eng.pool.refcount[p]) == 0 for p in free)


def test_spec_rollback_conserves_refcounts_and_reuse_is_bitexact():
    """After speculative rejections (pages rolled back), refcounts equal
    the table exactly, and a prefix-cache hit on a rolled-back slot
    decodes bit-exact vs a cold engine (acceptance criterion)."""
    cfg = _cfg()
    api, params = _params(cfg)
    rng = np.random.default_rng(35)
    # small pages so drafted blocks cross page boundaries and rejections
    # strand whole pages (which rollback must release)
    kw = dict(max_slots=2, max_seq=48, prefill_chunk=8, page_size=8,
              paged_kv=True, min_prefix=8)
    base = rng.integers(0, cfg.vocab, (12,)).tolist()
    eng = ServeEngine(cfg, params, spec_k=5, **kw)
    r1 = eng.submit(base, 14)
    eng.run()
    assert eng.stats["spec_drafted"] > eng.stats["spec_accepted"], \
        "workload produced no rejections; rollback path untested"
    _assert_refcounts_conserved(eng)
    # the retired slot's entry indexes prompt + output; extend it
    follow = base + r1.generated + rng.integers(0, cfg.vocab, (4,)).tolist()
    r2 = eng.submit(follow, 8)
    eng.run()
    st = eng.stats_summary()
    assert st["prefix_hits"] >= 1, "follow-up did not hit the rolled-back slot"
    _assert_refcounts_conserved(eng)
    cold_eng, cold = _serve(cfg, params, [follow], [8], spec_k=0,
                            prefix_cache=False, **kw)
    assert r2.generated == cold[0]


# ---------------------------------------------------------------------------
# randomized churn: refcounts exactly conserved, never underflow
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", params=SPEC_ARCHS)
def churn_engine(request):
    """One long-lived speculative paged engine per family (engines are
    expensive to compile; the churn invariant is stateless, so examples
    share the engine and keep mutating it)."""
    cfg = _cfg(request.param)
    api, params = _params(cfg)
    eng = ServeEngine(cfg, params, max_slots=2, max_seq=32,
                      prefill_chunk=8, page_size=8, paged_kv=True,
                      spec_k=3, min_prefix=8, trie_capacity=3)
    eng._churn_rng = np.random.default_rng(99)
    eng._churn_shared = [int(t) for t in
                         eng._churn_rng.integers(0, cfg.vocab, (12,))]
    return eng


@settings(max_examples=12, deadline=None)
@given(st.data())
def test_spec_churn_conserves_refcounts(churn_engine, data):
    """Satellite: a randomized admit / prefix-hit / spec-rollback / evict /
    retire sequence leaves PagePool refcounts exactly conserved (equal to
    the page-table ground truth) and never underflows, for GQA and MLA.
    Any underflow raises inside deref; any leak/drift trips the
    conservation check run after every operation."""
    eng = churn_engine
    rng = eng._churn_rng
    vocab = eng.cfg.vocab
    for _ in range(data.draw(st.integers(min_value=2, max_value=5))):
        op = data.draw(st.integers(min_value=0, max_value=3))
        # every argument is drawn on every iteration, whatever the op and
        # the shared engine's state, so a replay makes the same draws
        shared = data.draw(st.booleans())
        max_new = data.draw(st.integers(min_value=2, max_value=6))
        victim = data.draw(st.integers(min_value=0,
                                       max_value=eng.max_slots - 1))
        if op == 0 and len(eng.scheduler.pending) < 4:
            # submit: half the time extend the shared prefix (prefix-hit
            # admissions), otherwise a fresh random prompt (cold + trie
            # churn); repetitive tails make some drafts accept, random
            # ones make others reject (spec rollback)
            if shared:
                tail = [int(t) for t in rng.integers(0, vocab, (3,))]
                prompt = eng._churn_shared + tail
            else:
                prompt = [int(t) for t in rng.integers(0, vocab, (10,))]
            eng.submit(prompt, max_new)
        elif op == 1:
            eng.step()
        elif op == 2 and eng.scheduler.active:
            slots = sorted(eng.scheduler.active)
            eng.evict(slots[victim % len(slots)])
        elif op == 3:
            eng.run(max_steps=8)                # drain toward retirement
        _assert_refcounts_conserved(eng)
        _assert_request_clocks(eng)


def _assert_request_clocks(eng):
    """Each request's first token is counted once, by an admission, and
    its scheduler-clock stamps come in lifecycle order."""
    sched = eng.scheduler
    reqs = list(sched.pending) + list(sched.active.values()) + sched.finished
    firsts = sum(1 for r in reqs if r.first_token_t is not None)
    assert eng.stats["first_tokens"] == firsts <= eng.stats["admissions"]
    for r in reqs:
        stamps = [t for t in (r.submit_t, r.admit_t, r.first_token_t,
                              r.finish_t) if t is not None]
        assert stamps == sorted(stamps), r.rid
        assert r.first_token_t is None or r.admit_t is not None
        assert r.finish_t is None or r.first_token_t is not None


# ---------------------------------------------------------------------------
# scheduler: tokens-per-step-aware cost model + multi-token accounting
# ---------------------------------------------------------------------------

def test_scheduler_cost_model_prices_tokens_per_step():
    clk = lambda: 0.0
    sched = Scheduler(2, 256, prefill_chunk=8, clock=clk)
    sched.update_cost_model(chunk_s=0.0, step_s=0.01)
    req = Request(prompt=[1], max_new=40)
    seq_est = sched.est_service_s(req)
    assert seq_est == pytest.approx(40 * 0.01)
    # speculative decode emits 2.5 tokens/step: 40 tokens in 16 steps
    sched.update_cost_model(tokens_per_step=2.5)
    assert sched.est_service_s(req) == pytest.approx(16 * 0.01)
    assert sched.est_decode_s(0) == 0.0
    # rates below 1 are clamped (a step always emits at least one token)
    sched.update_cost_model(tokens_per_step=0.25)
    assert sched.est_tokens_per_step == 1.0


def test_scheduler_preemption_wait_uses_tokens_per_step():
    """A pending SLO'd request is NOT at risk when speculative throughput
    clears the running batch fast enough — preemption decisions must use
    the tokens-per-step-deflated wait estimate."""
    now = [0.0]
    sched = Scheduler(1, 256, prefill_chunk=8, clock=lambda: now[0])
    sched.update_cost_model(chunk_s=0.0, step_s=0.01)
    running = Request(prompt=[1], max_new=60)
    sched.submit(running)
    sched.admissions()
    sched.on_prefill(running, 5)
    urgent = Request(prompt=[2], max_new=1, slo_ms=450.0)
    sched.submit(urgent)
    # sequential estimate: ~59 steps * 10ms = 590ms wait > 450ms slack
    assert sched.maybe_preempt() == running.slot
    # at 4 tokens/step the batch clears in ~150ms: no preemption needed
    sched.update_cost_model(tokens_per_step=4.0)
    assert sched.maybe_preempt() is None


def test_scheduler_on_decode_tokens_multi_token_retire():
    sched = Scheduler(1, 64, prefill_chunk=8, clock=lambda: 0.0)
    req = Request(prompt=[1, 2], max_new=4, eos_id=9)
    sched.submit(req)
    sched.admissions()
    sched.on_prefill(req, 5)
    done = sched.on_decode_tokens({0: [6, 9, 7]})   # eos mid-block
    assert done == [req]
    assert req.generated == [5, 6, 9]               # 7 never appended
    assert req.pos == len(req.context) - 1          # invariant holds


def test_engine_reports_spec_stats_and_percentiles():
    cfg = _cfg()
    api, params = _params(cfg)
    rng = np.random.default_rng(36)
    pat = rng.integers(0, cfg.vocab, (4,)).tolist()
    eng, _ = _serve(cfg, params, [pat * 5], [12], spec_k=4, max_slots=2,
                    max_seq=48, prefill_chunk=8)
    st = eng.stats_summary()
    assert st["spec_k"] == 4
    assert 0.0 < st["spec_accept_rate"] <= 1.0
    assert st["tokens_per_step"] > 1.0
    assert 0.0 < st["spec_draft_hit_rate"] <= 1.0
    assert st["decode_step_p50_s"] > 0.0
    assert st["decode_step_p99_s"] >= st["decode_step_p50_s"]
