"""Randomized serve-churn invariant suite.

Extends the PR 5 spec-churn pattern (``tests/test_spec.py``) to the full
overload-hardened surface: random interleavings of admit / re-admit /
content-dedup / session turns / end-session / spec-rollback / degrade /
shed / evict / retire on paged GQA **and** MLA engines, with the complete
set of allocator invariants checked after every operation:

* pool refcounts exactly equal the ground truth (page-table occurrences
  PLUS session-snapshot occurrences — sessions hold one engine-owned
  reference per snapshot page);
* the free list is consistent (length matches ``free_count``, every
  member has refcount 0, no duplicates);
* the scratch page stays pinned at refcount 1 and never appears in any
  row or snapshot;
* the content-dedup index never points at a freed page, and every indexed
  digest still matches the page's ACTUAL bytes (an index entry that
  outlives a content change would silently corrupt a later admission);
* shed requests are retired-with-reason, never silently dropped.

Engines run with tiny pools, tiny pages, spec drafting, sessions, dedup
AND the degrade ladder on, so allocation pressure, rollback, snapshot
drops and shedding all fire inside the random walk.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.registry import get_config
from repro.models.common import init_params
from repro.models.registry import get_api
from repro.serve import ServeEngine

jax.config.update("jax_enable_x64", False)

CHURN_ARCHS = ["llama3.2-3b", "minicpm3-4b"]     # GQA + MLA families


def _ground_truth_refcounts(eng):
    """Per-page reference ground truth: occurrences across live page-table
    rows plus occurrences across session snapshots (the engine takes one
    pool ref per snapshot page)."""
    counts = np.zeros(eng.pool.num_pages, np.int64)
    for slot in range(eng.max_slots):
        for lp in range(eng.max_pages):
            p = int(eng.table[slot, lp])
            if p:
                counts[p] += 1
    for p in eng.sessions.snapshot_pages():
        counts[p] += 1
    return counts


def _assert_invariants(eng):
    counts = _ground_truth_refcounts(eng)
    # refcounts == ground truth, exactly, for every allocatable page
    for p in range(1, eng.pool.num_pages):
        assert int(eng.pool.refcount[p]) == counts[p], (
            f"page {p}: refcount {int(eng.pool.refcount[p])} != "
            f"{counts[p]} table+session occurrences")
    assert eng.pool.used_count == int((counts[1:] > 0).sum())
    # scratch pinned, never mapped
    assert int(eng.pool.refcount[0]) == 1
    assert counts[0] == 0
    # free lists consistent: size, refcounts, no duplicates, and every
    # shard's free pages stay inside that shard's block
    free = [p for fl in eng.pool._free for p in fl]
    assert len(free) == eng.pool.free_count
    assert len(set(free)) == len(free)
    assert all(int(eng.pool.refcount[p]) == 0 for p in free)
    for sh, fl in enumerate(eng.pool._free):
        assert all(eng.pool.shard_of(p) == sh for p in fl)
    # dedup index: never points at a freed page, digests never stale
    if eng.dedup is not None:
        for p in eng.dedup.pages():
            assert int(eng.pool.refcount[p]) > 0, (
                f"dedup index points at freed page {p}")
            assert eng._digest_fn(eng._page_bytes_of(p)) \
                == eng.dedup.digest_of(p), (
                f"dedup index holds a stale digest for page {p}")
    # shedding never silently drops: every shed landed in finished
    shed = [r for r in eng.scheduler.finished if r.shed_reason is not None]
    assert len(shed) == eng.scheduler.shed_count
    assert all(r.slo_met is False for r in shed)
    _assert_request_clocks(eng)


def _assert_request_clocks(eng):
    """Each request's first token is counted once, by an admission, and
    every request's scheduler-clock stamps come in lifecycle order (a
    shed request may never have been admitted)."""
    sched = eng.scheduler
    reqs = list(sched.pending) + list(sched.active.values()) + sched.finished
    firsts = sum(1 for r in reqs if r.first_token_t is not None)
    assert eng.stats["first_tokens"] == firsts <= eng.stats["admissions"]
    for r in reqs:
        stamps = [t for t in (r.submit_t, r.admit_t, r.first_token_t,
                              r.finish_t) if t is not None]
        assert stamps == sorted(stamps), r.rid
        assert r.first_token_t is None or r.admit_t is not None
        if r.finish_t is not None and r.shed_reason is None:
            assert r.first_token_t is not None


@pytest.fixture(scope="module", params=CHURN_ARCHS)
def churn_engine(request):
    """One long-lived engine per family with EVERYTHING on: paged KV,
    tiny pool (constant reclaim pressure), spec drafting, prefix trie,
    content dedup, sessions, degrade ladder.  Engines are expensive to
    compile; the invariants are stateless, so examples share the engine
    and keep mutating it."""
    cfg = get_config(request.param).reduced(dtype=jnp.float32)
    api = get_api(cfg)
    params = init_params(api.param_specs(cfg), jax.random.key(0))
    eng = ServeEngine(cfg, params, max_slots=2, max_seq=32,
                      prefill_chunk=8, page_size=8, paged_kv=True,
                      pool_pages=12, spec_k=3, min_prefix=8,
                      trie_capacity=3, page_dedup=True, degrade=True)
    # virtual clock: shed/pressure decisions must not depend on host speed
    eng._churn_clock = [0.0]
    eng.scheduler.clock = lambda: eng._churn_clock[0]
    eng._churn_rng = np.random.default_rng(99)
    eng._churn_shared = [int(t) for t in
                         eng._churn_rng.integers(0, cfg.vocab, (12,))]
    eng._churn_convs = ("conv-a", "conv-b")
    return eng


@settings(max_examples=12, deadline=None)
@given(st.data())
def test_churn_conserves_every_serve_invariant(churn_engine, data):
    """Tentpole satellite: a randomized admit / session-turn / dedup /
    spec-rollback / degrade / shed / evict / end-session / retire walk
    leaves refcounts equal to the table+session ground truth, the free
    list consistent, scratch pinned, and the trie/dedup indices never
    pointing at freed pages — for GQA and MLA page layouts."""
    eng = churn_engine
    rng = eng._churn_rng
    vocab = eng.cfg.vocab
    for _ in range(data.draw(st.integers(min_value=2, max_value=5))):
        op = data.draw(st.integers(min_value=0, max_value=6))
        # every argument is drawn on every iteration, whatever the op and
        # the shared engine's state, so a replay makes the same draws
        shared = data.draw(st.booleans())
        slo = data.draw(st.sampled_from([None, 50.0, 5000.0]))
        max_new = data.draw(st.integers(min_value=2, max_value=6))
        conv = eng._churn_convs[data.draw(st.integers(min_value=0,
                                                      max_value=1))]
        victim = data.draw(st.integers(min_value=0,
                                       max_value=eng.max_slots - 1))
        if op == 0 and len(eng.scheduler.pending) < 4:
            # one-shot submit: half shared-prefix (trie/dedup hits), half
            # random (cold churn); occasionally with a tight virtual SLO
            # so overload pressure and shedding actually fire
            if shared:
                tail = [int(t) for t in rng.integers(0, vocab, (3,))]
                prompt = eng._churn_shared + tail
            else:
                prompt = [int(t) for t in rng.integers(0, vocab, (10,))]
            eng.submit(prompt, max_new, slo_ms=slo)
        elif op == 1 and len(eng.scheduler.pending) < 4:
            # session turn: histories grow across examples; start the
            # conversation over before it outgrows max_seq
            sess = eng.sessions.get(conv)
            if sess is not None and len(sess.history) > 20:
                eng.end_session(conv)
            eng.submit_turn(conv, [int(t) for t in
                                   rng.integers(0, vocab, (4,))], 2)
        elif op == 2:
            eng._churn_clock[0] += 0.05     # let deadlines actually pass
            eng.step()
        elif op == 3 and eng.scheduler.active:
            slots = sorted(eng.scheduler.active)
            eng.evict(slots[victim % len(slots)])
        elif op == 4:
            eng.end_session(conv)
        elif op == 5:
            eng._churn_clock[0] += 1.0      # burst of virtual time: every
            eng.step()                      # tight-SLO request goes doomed
        elif op == 6:
            eng._churn_clock[0] += 0.01
            eng.run(max_steps=8)            # drain toward retirement
        _assert_invariants(eng)


@pytest.fixture(scope="module", params=CHURN_ARCHS)
def tree_churn_engine(request):
    """The same everything-on engine but speculating through the token-
    tree path (``spec_mode="auto"`` so the reconfigurator flips between
    chain- and tree-shaped steps inside the walk)."""
    cfg = get_config(request.param).reduced(dtype=jnp.float32)
    api = get_api(cfg)
    params = init_params(api.param_specs(cfg), jax.random.key(0))
    eng = ServeEngine(cfg, params, max_slots=2, max_seq=32,
                      prefill_chunk=8, page_size=8, paged_kv=True,
                      pool_pages=12, spec_k=3, spec_mode="auto",
                      spec_tree_nodes=6, spec_branch=2, min_prefix=8,
                      trie_capacity=3, page_dedup=True, degrade=True)
    eng._churn_clock = [0.0]
    eng.scheduler.clock = lambda: eng._churn_clock[0]
    eng._churn_rng = np.random.default_rng(77)
    eng._churn_shared = [int(t) for t in
                        eng._churn_rng.integers(0, cfg.vocab, (12,))]
    eng._churn_convs = ("conv-a", "conv-b")
    return eng


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_tree_churn_conserves_every_serve_invariant(tree_churn_engine,
                                                    data):
    """Satellite: the same randomized walk under tree speculation.  Tree
    verification writes every drafted-node row to the scratch page, so a
    rejected branch is refcount-invisible by construction — the ground
    truth the invariant checks pin after every operation."""
    eng = tree_churn_engine
    rng = eng._churn_rng
    vocab = eng.cfg.vocab
    for _ in range(data.draw(st.integers(min_value=2, max_value=5))):
        op = data.draw(st.integers(min_value=0, max_value=4))
        # every argument is drawn on every iteration (see above)
        shared = data.draw(st.booleans())
        max_new = data.draw(st.integers(min_value=2, max_value=6))
        conv = eng._churn_convs[data.draw(st.integers(min_value=0,
                                                      max_value=1))]
        victim = data.draw(st.integers(min_value=0,
                                       max_value=eng.max_slots - 1))
        if op == 0 and len(eng.scheduler.pending) < 4:
            # repetitive tails accept deep paths, random ones reject at
            # the root — both tree outcomes churn inside the walk
            if shared:
                tail = [int(t) for t in rng.integers(0, vocab, (3,))]
                prompt = eng._churn_shared + tail
            else:
                prompt = [int(t) for t in rng.integers(0, vocab, (10,))]
            eng.submit(prompt, max_new)
        elif op == 1 and len(eng.scheduler.pending) < 4:
            sess = eng.sessions.get(conv)
            if sess is not None and len(sess.history) > 20:
                eng.end_session(conv)
            eng.submit_turn(conv, [int(t) for t in
                                   rng.integers(0, vocab, (4,))], 2)
        elif op == 2:
            eng._churn_clock[0] += 0.05
            eng.step()
        elif op == 3 and eng.scheduler.active:
            slots = sorted(eng.scheduler.active)
            eng.evict(slots[victim % len(slots)])
        elif op == 4:
            eng._churn_clock[0] += 0.01
            eng.run(max_steps=8)
        _assert_invariants(eng)


def test_tree_churn_walk_exercised_the_tree_paths(tree_churn_engine):
    """Meta-check on the shared tree engine: tree steps actually ran,
    the reconfigurator actually decided, and NO page was ever rolled
    back — tree rejection lands on scratch, so the chain path's rollback
    counter must stay untouched."""
    eng = tree_churn_engine
    assert eng.stats["admissions"] > 0
    assert eng.stats["spec_tree_steps"] > 0
    assert eng.stats["spec_shape_chain"] + eng.stats["spec_shape_tree"] > 0
    assert eng.stats["spec_pages_rolled_back"] == 0
    _assert_invariants(eng)


def test_churn_walk_exercised_the_interesting_paths(churn_engine):
    """Meta-check (runs after the walks on the shared engine): the random
    walk actually drove the machinery it claims to test — admissions,
    speculative rollback pressure, session snapshots and reclaim all left
    footprints.  Guards against the suite silently degenerating into
    no-ops after a refactor."""
    eng = churn_engine
    assert eng.stats["admissions"] > 0
    assert eng.stats["spec_steps"] > 0
    assert eng.stats["session_turns"] > 0
    assert eng.scheduler.finished, "nothing ever retired"
    _assert_invariants(eng)
