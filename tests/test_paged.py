"""Paged KV allocation tests: page-pool invariants, zero-copy prefix
sharing, copy-on-write isolation, OOM deferral, trie LRU eviction, and
bit-exact equivalence of the paged engine against the contiguous one."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import paging
from repro.models.common import ParamSpec, init_params
from repro.models.registry import get_api
from repro.serve import (PagePool, PrefixTrie, Request, Scheduler,
                         ServeEngine, pageable, paged_state_specs,
                         state_zeros)

jax.config.update("jax_enable_x64", False)


def _cfg(arch_id="llama3.2-3b", **over):
    return get_config(arch_id).reduced(dtype=jnp.float32, **over)


def _params(cfg, seed=0):
    api = get_api(cfg)
    return api, init_params(api.param_specs(cfg), jax.random.key(seed))


# ---------------------------------------------------------------------------
# page pool (pure host logic)
# ---------------------------------------------------------------------------

def test_page_pool_alloc_ref_deref():
    pool = PagePool(4)                       # pages 1..3 allocatable
    assert pool.free_count == 3 and pool.used_count == 0
    a = pool.alloc()
    b = pool.alloc()
    assert a == 1 and b == 2 and pool.used_count == 2
    pool.ref(a)                              # shared: refcount 2
    assert not pool.deref(a)                 # still referenced elsewhere
    assert pool.deref(a)                     # now actually freed
    assert pool.free_count == 2
    # freed pages are reused
    c = pool.alloc()
    assert c in (1, 3)


def test_page_pool_refcount_never_negative():
    pool = PagePool(3)
    p = pool.alloc()
    pool.deref(p)
    with pytest.raises(ValueError):
        pool.deref(p)                        # underflow
    with pytest.raises(ValueError):
        pool.deref(0)                        # scratch is pinned
    with pytest.raises(ValueError):
        pool.ref(0)                          # scratch cannot be shared
    with pytest.raises(ValueError):
        pool.ref(2)                          # never allocated


def test_page_pool_exhaustion_returns_sentinel():
    pool = PagePool(2)
    assert pool.alloc() == 1
    assert pool.alloc() == -1                # OOM: sentinel, not exception
    assert pool.oom_events == 1
    with pytest.raises(ValueError):
        PagePool(1)                          # scratch-only pool is useless


# ---------------------------------------------------------------------------
# pooled layout + gather/scatter primitives
# ---------------------------------------------------------------------------

def test_paged_state_specs_layout_and_gating():
    for arch in ("llama3.2-3b", "minicpm3-4b"):
        cfg = _cfg(arch)
        specs = get_api(cfg).decode_state_specs(cfg, 2, 32)
        assert pageable(specs, 16)
        pspecs = paged_state_specs(specs, 16, 5)
        for s in jax.tree.leaves(pspecs,
                                 is_leaf=lambda x: isinstance(x, ParamSpec)):
            pp = s.axes.index("phys_page")
            assert s.axes[pp + 1] == "page_seq"
            assert s.shape[pp] == 5 and s.shape[pp + 1] == 16
            assert "batch" not in s.axes and "kv_seq" not in s.axes
    for arch in ("falcon-mamba-7b", "zamba2-1.2b"):
        cfg = _cfg(arch)
        specs = get_api(cfg).decode_state_specs(cfg, 2, 32)
        assert not pageable(specs, 16)
        with pytest.raises(ValueError):
            paged_state_specs(specs, 16, 5)
    # page size must divide the capacity
    cfg = _cfg()
    specs = get_api(cfg).decode_state_specs(cfg, 2, 24)
    assert not pageable(specs, 16)


def test_gather_scatter_roundtrip():
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(5, 4, 3)), jnp.float32)
    pages = jnp.asarray([[2, 4], [1, 3]], jnp.int32)     # 2 slots, 2 pages
    view = paging.gather_pages(pool, pages)
    assert view.shape == (2, 8, 3)
    np.testing.assert_array_equal(np.asarray(view[0, :4]),
                                  np.asarray(pool[2]))
    np.testing.assert_array_equal(np.asarray(view[1, 4:]),
                                  np.asarray(pool[3]))
    # scatter one row per slot at positions crossing the page boundary
    rows = jnp.asarray(rng.normal(size=(2, 1, 3)), jnp.float32)
    pos = jnp.asarray([[5], [2]], jnp.int32)   # slot0 -> page 4 off 1
    out = paging.scatter_token_rows(pool, pages, rows, pos)
    np.testing.assert_array_equal(np.asarray(out[4, 1]),
                                  np.asarray(rows[0, 0]))
    np.testing.assert_array_equal(np.asarray(out[1, 2]),
                                  np.asarray(rows[1, 0]))
    # every other element untouched
    mask = np.ones((5, 4), bool)
    mask[4, 1] = mask[1, 2] = False
    np.testing.assert_array_equal(np.asarray(out)[mask],
                                  np.asarray(pool)[mask])


# ---------------------------------------------------------------------------
# engine equivalence: paged allocation == contiguous, bit-exact tokens
# ---------------------------------------------------------------------------

PAGED_ARCHS = ["llama3.2-3b", "minicpm3-4b"]     # GQA + MLA families


def _run_engine(cfg, params, prompts, gens, **kw):
    eng = ServeEngine(cfg, params, **kw)
    reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    eng.run()
    return eng, [r.generated for r in reqs]


@pytest.mark.parametrize("arch_id", PAGED_ARCHS)
def test_paged_engine_tokens_bitexact_vs_contiguous(arch_id):
    """Staggered continuous-batching workload (with slot refill) decodes
    the very same greedy tokens under paged allocation as under the
    contiguous copy_slot engine."""
    cfg = _cfg(arch_id)
    api, params = _params(cfg)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab, (n,)).tolist()
               for n in (7, 12, 3, 9)]
    gens = [5, 4, 8, 6]
    kw = dict(max_slots=2, max_seq=32, prefill_chunk=8, min_prefix=8)
    contig, tok_c = _run_engine(cfg, params, prompts, gens,
                                paged_kv=False, **kw)
    paged, tok_p = _run_engine(cfg, params, prompts, gens,
                               paged_kv=True, **kw)
    assert not contig.paged and paged.paged
    assert tok_p == tok_c
    assert paged.stats["admissions"] == len(prompts)


# ---------------------------------------------------------------------------
# split-K core: grouped-query contraction == repeated-KV formulation
# ---------------------------------------------------------------------------

def _repeated_kv_reference(q, k, v, valid, hd):
    """The repeated-KV formula in f32: each KV head broadcast to its
    query group, one softmax over the whole view, then PV."""
    rep = q.shape[2] // k.shape[2]
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bchd,bshd->bhcs", q, k) / np.sqrt(hd)
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhcs,bshd->bchd", probs, v)
    return out.reshape(q.shape[0], q.shape[1], -1)


# (rep, C, mask, dtype, padded): decode (C=1) and a K=4 verify block
# (C=5) at MHA, llama3.2-3b's and GLM-4's group sizes, one tree block
# under its ancestor mask, and one group padded as on a model axis that
# does not divide the head count.  f32 is held to reassociation of the
# page sums (1e-5).  bf16 rounds the scores, the probabilities fed to
# PV and the output to an 8-bit mantissa, a relative error of ~2**-8 on
# each, so it is held to 3e-2 on unit-scale values.
SPLITK_CASES = [
    (1, 1, "causal", jnp.float32, False),
    (1, 5, "causal", jnp.float32, False),
    (3, 1, "causal", jnp.float32, False),
    (3, 5, "causal", jnp.float32, False),
    (16, 1, "causal", jnp.float32, False),
    (16, 5, "causal", jnp.float32, False),
    (16, 5, "tree", jnp.float32, False),
    (3, 5, "causal", jnp.float32, True),
    (16, 1, "causal", jnp.bfloat16, False),
    (16, 5, "tree", jnp.bfloat16, False),
]


@pytest.mark.parametrize("rep,c,mask,dtype,padded", SPLITK_CASES)
def test_splitk_attend_grouped_matches_repeated_kv(monkeypatch, rep, c,
                                                   mask, dtype, padded):
    from repro.models import attention
    b, hkv, hd, page, smax = 2, 2, 32, 16, 48      # 3 pages: tree pads
    cfg = dataclasses.replace(get_config("llama3.2-3b"),
                              n_heads=hkv * rep, n_kv_heads=hkv,
                              head_dim=hd)
    if padded:     # one zero query head appended inside each group
        monkeypatch.setattr(attention, "tp_head_pad", lambda _: hkv)
    kq, kk, kv = jax.random.split(jax.random.key(rep * 10 + c), 3)
    q = jax.random.normal(kq, (b, c, hkv * rep, hd), dtype)
    k = jax.random.normal(kk, (b, smax, hkv, hd), dtype)
    v = jax.random.normal(kv, (b, smax, hkv, hd), dtype)
    cur = jnp.array([3, 40], jnp.int32)             # one slot near the end
    if mask == "causal":
        pos = cur[:, None] + jnp.arange(c, dtype=jnp.int32)
        valid = attention.causal_valid(pos, smax)
    else:          # root, two children, one grandchild under each
        parents = jnp.tile(jnp.array([[-1, 0, 0, 1, 2]], jnp.int32), (b, 1))
        valid = attention.tree_valid(cur, parents,
                                     jnp.full((b,), c, jnp.int32), smax)
    got = attention._splitk_attend(q, k, v, valid, cfg, page)
    want = _repeated_kv_reference(q, k, v, valid, hd)
    assert got.shape == (b, c, hkv * rep * hd) and got.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=tol, atol=tol)


@pytest.mark.parametrize("arch_id", PAGED_ARCHS)
def test_paged_prefix_hit_shares_pages_zero_copy(arch_id):
    """Shared-prefix workload: hits share whole pages by reference (only
    the partial boundary page is copied) and still decode the same greedy
    tokens as both the contiguous engine and a cold one."""
    cfg = _cfg(arch_id)
    api, params = _params(cfg)
    rng = np.random.default_rng(22)
    system = rng.integers(0, cfg.vocab, (16,)).tolist()   # exactly 1 page
    prompts = [system + rng.integers(0, cfg.vocab, (4,)).tolist()
               for _ in range(3)]
    gens = [4] * len(prompts)
    kw = dict(max_slots=2, max_seq=48, prefill_chunk=8, min_prefix=8)
    cold, tok_cold = _run_engine(cfg, params, prompts, gens,
                                 prefix_cache=False, **kw)
    contig, tok_c = _run_engine(cfg, params, prompts, gens,
                                paged_kv=False, **kw)
    paged, tok_p = _run_engine(cfg, params, prompts, gens,
                               paged_kv=True, **kw)
    assert tok_p == tok_c == tok_cold
    sc, sp = contig.stats_summary(), paged.stats_summary()
    assert sp["prefix_hits"] == sc["prefix_hits"] >= 2
    # a page-aligned prefix is shared by pure reference: ZERO bytes copied
    # (a cross-slot hit shares >= 1 page; a same-slot hit keeps its row)
    assert sp["pages_shared"] >= 1 and sp["pages_cow"] == 0
    assert sp["prefix_bytes_copied"] == 0
    # the contiguous engine copied whole slots for its cross-slot hits
    assert sc["prefix_bytes_copied"] > 0


def test_cow_isolates_boundary_page():
    """A sharer's writes land in its own copy-on-write boundary page: the
    source entry stays reusable and produces cold-identical tokens for a
    third request."""
    cfg = _cfg()
    api, params = _params(cfg)
    rng = np.random.default_rng(23)
    system = rng.integers(0, cfg.vocab, (12,)).tolist()   # < one page
    tail_a = rng.integers(0, cfg.vocab, (4,)).tolist()
    tail_b = rng.integers(0, cfg.vocab, (4,)).tolist()

    def cold(prompt, gen=6):
        _, toks = _run_engine(cfg, params, [prompt], [gen],
                              prefix_cache=False, max_slots=2, max_seq=48,
                              prefill_chunk=8)
        return toks[0]

    eng = ServeEngine(cfg, params, max_slots=2, max_seq=48,
                      prefill_chunk=8, min_prefix=8, paged_kv=True)
    r1 = eng.submit(system, 20)               # slot 0, stays live
    eng.step()
    eng.step()
    r2 = eng.submit(system + tail_a, 6)       # slot 1: cross-slot hit,
    while not r2.done:                        # CoW of page 0 only
        eng.step()
    assert eng.stats["pages_cow"] == 1
    r3 = eng.submit(system + tail_b, 6)       # slot 1 again: source slot 0
    eng.run()                                 # is STILL decoding into its
    assert eng.stats["pages_cow"] == 2        # own boundary page
    assert eng.stats["pages_shared"] == 0     # no full page in a 12-token
    assert r1.generated == cold(system, 20)   # prefix
    assert r2.generated == cold(system + tail_a)
    assert r3.generated == cold(system + tail_b)


def test_evicting_source_slot_preserves_sharer():
    """Overwriting the slot that first wrote a shared page must not free
    it while a sharer still references it: the sharer's remaining decode
    is bit-exact vs a cold prefill."""
    cfg = _cfg()
    api, params = _params(cfg)
    rng = np.random.default_rng(24)
    system = rng.integers(0, cfg.vocab, (20,)).tolist()   # crosses page 0
    tail = rng.integers(0, cfg.vocab, (4,)).tolist()
    other = rng.integers(0, cfg.vocab, (9,)).tolist()

    eng = ServeEngine(cfg, params, max_slots=2, max_seq=48,
                      prefill_chunk=8, min_prefix=8, paged_kv=True)
    r1 = eng.submit(system, 10)               # slot 0, stays live a while
    eng.step()
    eng.step()
    shared_page = int(eng.table[0, 0])
    assert shared_page > 0
    r2 = eng.submit(system + tail, 16)        # slot 1: shares r1's page 0
    eng.step()                                # by reference
    assert r2.slot == 1
    assert int(eng.pool.refcount[shared_page]) == 2
    assert int(eng.table[1, 0]) == shared_page
    while not r1.done:                        # r1 retires; its row (and
        eng.step()                            # trie entry) keep the ref
    assert int(eng.pool.refcount[shared_page]) == 2
    r3 = eng.submit(other, 2)                 # overwrites slot 0 while r2
    eng.step()                                # is still decoding
    assert r3.generated and not r2.done
    # the page outlived its original slot: r2's reference keeps it alive
    assert int(eng.pool.refcount[shared_page]) == 1
    eng.run()
    _, toks = _run_engine(cfg, params, [system + tail], [16],
                          prefix_cache=False, max_slots=2, max_seq=48,
                          prefill_chunk=8)
    assert r2.generated == toks[0]


def test_refcounts_conserved_after_mixed_workload():
    """After draining a mixed share/evict workload, every allocated page's
    refcount equals the number of table rows mapping it."""
    cfg = _cfg()
    api, params = _params(cfg)
    rng = np.random.default_rng(25)
    system = rng.integers(0, cfg.vocab, (20,)).tolist()
    prompts = ([system + rng.integers(0, cfg.vocab, (4,)).tolist()
                for _ in range(3)]
               + [rng.integers(0, cfg.vocab, (10,)).tolist()])
    eng, _ = _run_engine(cfg, params, prompts, [4] * 4, paged_kv=True,
                         max_slots=2, max_seq=48, prefill_chunk=8,
                         min_prefix=8)
    counts = np.zeros(eng.pool.num_pages, np.int64)
    for slot in range(eng.max_slots):
        for lp in range(eng.max_pages):
            counts[int(eng.table[slot, lp])] += 1
    for p in range(1, eng.pool.num_pages):
        assert int(eng.pool.refcount[p]) == counts[p], p
    assert eng.pool.used_count == int((counts[1:] > 0).sum())


def test_oom_admissions_deferred_not_dropped():
    """A pool too small for two concurrent requests defers the second
    admission until the first one's pages are reclaimed — both requests
    finish with full budgets and cold-identical tokens."""
    cfg = _cfg()
    api, params = _params(cfg)
    rng = np.random.default_rng(26)
    prompts = [rng.integers(0, cfg.vocab, (18,)).tolist() for _ in range(2)]
    for prefix_cache in (True, False):
        eng = ServeEngine(cfg, params, max_slots=2, max_seq=32,
                          prefill_chunk=8, paged_kv=True, pool_pages=2,
                          prefix_cache=prefix_cache)
        reqs = [eng.submit(p, 4) for p in prompts]
        eng.run(max_steps=200)
        assert all(len(r.generated) == 4 for r in reqs), prefix_cache
        assert eng.stats["oom_deferred"] >= 1
        for r in reqs:
            _, toks = _run_engine(cfg, params, [list(r.prompt)], [4],
                                  prefix_cache=False, max_slots=1,
                                  max_seq=32, prefill_chunk=8)
            assert r.generated == toks[0]


def test_pool_too_small_for_one_request_raises():
    cfg = _cfg()
    api, params = _params(cfg)
    eng = ServeEngine(cfg, params, max_slots=1, max_seq=32,
                      prefill_chunk=8, paged_kv=True, pool_pages=1)
    eng.submit(list(range(18)), 2)            # needs 2 pages, pool has 1
    with pytest.raises(RuntimeError):
        eng.run(max_steps=10)


# ---------------------------------------------------------------------------
# trie LRU capacity + engine validation + scheduler probe
# ---------------------------------------------------------------------------

def test_prefix_trie_lru_capacity():
    t = PrefixTrie(capacity=2)
    t.insert(0, [1, 2, 3])
    t.insert(1, [4, 5])
    t.longest_match([1, 2])                   # touches slot 0
    t.insert(2, [6, 7])                       # evicts LRU -> slot 1
    assert t.evictions == 1
    assert t.tokens(1) is None and t.tokens(0) == [1, 2, 3]
    # probes must not promote entries
    t.longest_match([1, 2], touch=False)
    t.insert(3, [8])                          # LRU is now slot 0
    assert t.tokens(0) is None and t.evictions == 2
    with pytest.raises(ValueError):
        PrefixTrie(capacity=0)


def test_engine_trie_capacity_reports_evictions():
    cfg = _cfg()
    api, params = _params(cfg)
    rng = np.random.default_rng(27)
    prompts = [rng.integers(0, cfg.vocab, (10,)).tolist() for _ in range(3)]
    eng, _ = _run_engine(cfg, params, prompts, [2] * 3, max_slots=3,
                         max_seq=32, prefill_chunk=8, trie_capacity=1)
    st = eng.stats_summary()
    assert st["trie_evictions"] >= 2
    assert len(eng.prefix) <= 1


def test_live_slot_trie_eviction_does_not_strand_pages():
    """Capacity-evicting a LIVE slot's trie entry must not leak its pages
    forever: the entry is gone (so LRU reclaim will never see the slot),
    so its row must be released the moment the request retires."""
    cfg = _cfg()
    api, params = _params(cfg)
    rng = np.random.default_rng(28)
    p1 = rng.integers(0, cfg.vocab, (18,)).tolist()       # 2 pages
    p2 = rng.integers(0, cfg.vocab, (18,)).tolist()
    eng = ServeEngine(cfg, params, max_slots=2, max_seq=32,
                      prefill_chunk=8, paged_kv=True, trie_capacity=1)
    r1 = eng.submit(p1, 12)                   # slot 0, stays live
    eng.step()
    eng.step()
    r2 = eng.submit(p2, 2)                    # slot 1: its insert LRU-
    eng.step()                                # evicts slot 0's LIVE entry
    assert eng.prefix.length(0) is None
    assert 0 in eng.scheduler.active          # ...which must not release
    assert int(eng.table[0, 0]) > 0           # the live row
    eng.run()
    assert len(r1.generated) == 12
    # r1 retired with no trie entry: its pages were released, not stranded
    assert not eng.table[0].any()
    # r2's row is still indexed (the one capacity slot) and so retained
    assert eng.prefix.length(1) is not None and eng.table[1].any()


def test_engine_paged_validation_errors():
    cfg = _cfg()
    api, params = _params(cfg)
    with pytest.raises(ValueError, match="divide"):
        ServeEngine(cfg, params, max_seq=32, page_size=12)
    with pytest.raises(ValueError, match="page_size > 0"):
        ServeEngine(cfg, params, max_seq=24, paged_kv=True)
    ssm = _cfg("falcon-mamba-7b")
    _, sparams = _params(ssm)
    with pytest.raises(ValueError, match="not pageable"):
        ServeEngine(ssm, sparams, max_seq=32, paged_kv=True)
    # auto mode degrades gracefully instead of raising
    eng = ServeEngine(ssm, sparams, max_seq=32)
    assert not eng.paged


def test_scheduler_reuse_probe_discounts_resident_prefix():
    """The cost model prices a resident prefix at ~0, so the eviction
    candidate prefers the victim whose pages are shared (cheap requeue)."""
    clk = lambda: 0.0
    sched = Scheduler(2, 64, prefill_chunk=8, clock=clk,
                      reuse_probe=lambda ctx: 16 if ctx[0] == 1 else 0)
    sched.update_cost_model(chunk_s=0.1, step_s=0.01)
    shared = sched.submit(Request(prompt=[1] * 16, max_new=4, slo_ms=5000))
    private = sched.submit(Request(prompt=[2] * 16, max_new=4, slo_ms=5000))
    # shared re-prefills 1 minimum chunk; private re-prefills 2 chunks
    assert sched.est_service_s(shared) < sched.est_service_s(private)
    sched.admissions()
    sched.on_prefill(shared, 9)
    sched.on_prefill(private, 9)
    assert sched.eviction_candidate() == shared.slot


# ---------------------------------------------------------------------------
# page-content dedup: interior spans the prefix trie cannot see
# ---------------------------------------------------------------------------

def _dedup_cfg():
    """1-layer config: layer-0 KV rows are a pure function of
    (token, position), so equal interior content at equal positions means
    byte-identical pages — the regime content dedup can actually hit."""
    return _cfg(n_layers=1)


def _dedup_prompts(cfg, rng, n=3, head=16, span=32):
    """``n`` prompts: one page of unique tokens (distinct first token —
    the prefix trie matches zero leading tokens across requests), then
    the SAME ``span``-token run at the same interior positions."""
    shared = rng.integers(0, cfg.vocab, (span,)).tolist()
    prompts = []
    for i in range(n):
        h = rng.integers(0, cfg.vocab, (head,)).tolist()
        h[0] = i
        prompts.append(h + shared)
    return prompts


def _dedup_kw(**over):
    kw = dict(max_slots=2, max_seq=64, prefill_chunk=16, page_size=16,
              paged_kv=True, pool_pages=24, min_prefix=8)
    kw.update(over)
    return kw


def _conserved_with_dedup(eng):
    """Pool refcounts equal the table ground truth, and the dedup index
    never points at a freed page."""
    counts = np.zeros(eng.pool.num_pages, np.int64)
    for slot in range(eng.max_slots):
        for lp in range(eng.max_pages):
            p = int(eng.table[slot, lp])
            if p:
                counts[p] += 1
    for p in range(1, eng.pool.num_pages):
        assert int(eng.pool.refcount[p]) == counts[p], p
    assert int(eng.pool.refcount[0]) == 1
    if eng.dedup is not None:
        for p in eng.dedup.pages():
            assert int(eng.pool.refcount[p]) > 0, (
                f"dedup index points at freed page {p}")


def test_dedup_interior_span_shared_and_bitexact():
    """Tentpole: admissions whose shared content sits at positions >=
    page_size — invisible to the prefix trie by construction — share
    whole pages through the content index, bit-exact vs dedup off."""
    cfg = _dedup_cfg()
    api, params = _params(cfg)
    rng = np.random.default_rng(41)
    prompts = _dedup_prompts(cfg, rng)
    gens = [4] * len(prompts)
    off, tok_off = _run_engine(cfg, params, prompts, gens, **_dedup_kw())
    on, tok_on = _run_engine(cfg, params, prompts, gens,
                             **_dedup_kw(page_dedup=True))
    assert tok_on == tok_off, "page dedup changed greedy tokens"
    st = on.stats_summary()
    assert st["prefix_hits"] == 0, "trie hit — the workload no longer " \
        "isolates interior-span dedup"
    assert st["dedup_hits"] >= len(prompts) - 1
    assert st["dedup_pages_per_hit"] >= 1.0
    assert st["dedup_hash_collisions"] == 0
    _conserved_with_dedup(on)
    # dedup actually reduced resident pages vs the dedup-off engine
    assert on.pool.used_count < off.pool.used_count


@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_dedup_quantized_pages_hash_codes_and_scales(kv_dtype):
    """Quantized pools dedup on (codes, scales) page content: identical
    interior spans still share, and tokens stay bit-exact vs the same
    dtype with dedup off (dedup never changes content, any dtype)."""
    cfg = _dedup_cfg()
    api, params = _params(cfg)
    rng = np.random.default_rng(42)
    prompts = _dedup_prompts(cfg, rng)
    gens = [4] * len(prompts)
    off, tok_off = _run_engine(cfg, params, prompts, gens,
                               **_dedup_kw(kv_dtype=kv_dtype))
    on, tok_on = _run_engine(cfg, params, prompts, gens,
                             **_dedup_kw(kv_dtype=kv_dtype,
                                         page_dedup=True))
    assert tok_on == tok_off
    st = on.stats_summary()
    assert st["dedup_hits"] >= len(prompts) - 1
    assert st["dedup_hash_collisions"] == 0
    _conserved_with_dedup(on)


def test_dedup_hash_collision_falls_back_to_byte_compare():
    """A colliding digest is only a CANDIDATE: the full byte compare
    refutes it, the collision is counted, and no page is wrongly shared
    (tokens bit-exact, refcounts conserved).  Forced by injecting a
    constant digest function, the worst possible hash."""
    cfg = _dedup_cfg()
    api, params = _params(cfg)
    rng = np.random.default_rng(43)
    prompts = _dedup_prompts(cfg, rng)
    gens = [4] * len(prompts)
    _, tok_off = _run_engine(cfg, params, prompts, gens, **_dedup_kw())
    eng = ServeEngine(cfg, params, **_dedup_kw(page_dedup=True))
    eng._digest_fn = lambda b: b"\x00" * 16
    reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    eng.run()
    assert [r.generated for r in reqs] == tok_off
    st = eng.stats_summary()
    # every unique head page collides with every other indexed page; the
    # byte compare must have refuted those while still sharing the
    # genuinely identical interior span
    assert st["dedup_hash_collisions"] > 0
    assert st["dedup_hits"] >= len(prompts) - 1
    _conserved_with_dedup(eng)


def test_dedup_detach_on_inplace_readmission_keeps_sharers_intact():
    """Re-admitting through a retired slot's own row (in_place) must not
    write through pages other rows share by content: shared pages in the
    overwrite span are detached (boundary page copy-on-write, fully
    rewritten pages replaced fresh), tokens stay cold-exact and the index
    never points at a freed page."""
    cfg = _dedup_cfg()
    api, params = _params(cfg)
    rng = np.random.default_rng(44)
    prompts = _dedup_prompts(cfg, rng, n=2)
    eng = ServeEngine(cfg, params, **_dedup_kw(page_dedup=True))
    r1, r2 = [eng.submit(p, 4) for p in prompts]
    eng.run()
    assert eng.stats["dedup_hits"] >= 1, "setup never shared a page"
    _conserved_with_dedup(eng)
    # same head as r1 up to a mid-page point, then diverge: the trie
    # matches r1's retired row (src == slot, in-place), and the overwrite
    # span crosses the dedup-shared interior pages
    follow = prompts[0][:24] + rng.integers(0, cfg.vocab, (12,)).tolist()
    r3 = eng.submit(follow, 4)
    eng.run()
    _conserved_with_dedup(eng)
    cold = ServeEngine(cfg, params, **_dedup_kw(prefix_cache=False))
    c3 = cold.submit(list(follow), 4)
    cold.run()
    assert r3.generated == c3.generated


def test_dedup_index_lru_capacity_and_discard():
    """PageDedupIndex host unit: candidates by digest, LRU capacity
    eviction, discard on free."""
    from repro.serve import PageDedupIndex
    idx = PageDedupIndex(capacity=2)
    idx.insert(1, b"a")
    idx.insert(2, b"a")
    assert idx.candidates(b"a") == [1, 2] and len(idx) == 2
    idx.insert(3, b"b")                       # capacity 2: evicts LRU
    assert idx.evictions == 1 and len(idx) == 2
    assert 3 in idx.pages()
    assert idx.discard(3) and not idx.discard(3)
    assert idx.candidates(b"b") == []
    # re-inserting a page replaces its old digest entry
    idx.insert(2, b"c")
    assert idx.digest_of(2) == b"c" and idx.candidates(b"a") != [1, 2]


def test_dedup_requires_paged_engine():
    cfg = _cfg()
    api, params = _params(cfg)
    with pytest.raises(ValueError, match="requires the paged engine"):
        ServeEngine(cfg, params, max_seq=32, paged_kv=False,
                    page_dedup=True)
    # auto mode: dedup silently off on an unpageable family
    ssm = _cfg("falcon-mamba-7b")
    _, sparams = _params(ssm)
    eng = ServeEngine(ssm, sparams, max_seq=32, page_dedup=True)
    assert not eng.paged and eng.dedup is None
