"""In-graph sampling tests: greedy fast-path exactness, truncation
semantics, and restart determinism of the stateless per-request PRNG
stream (same seed + same SamplingParams => identical tokens across
engine rebuilds; temperature=0 => bit-exact with the greedy engine)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.common import init_params
from repro.models.registry import get_api
from repro.serve import SamplingParams, ServeEngine, sample_tokens
from repro.serve.sampling import _sample_row

jax.config.update("jax_enable_x64", False)


def _cfg(arch_id="llama3.2-3b", **over):
    return get_config(arch_id).reduced(dtype=jnp.float32, **over)


def _params(cfg, seed=0):
    api = get_api(cfg)
    return api, init_params(api.param_specs(cfg), jax.random.key(seed))


def _lanes(b, temperature=1.0, top_k=0, top_p=1.0, seed=0, idx=0):
    return (jnp.full((b,), temperature, jnp.float32),
            jnp.full((b,), top_k, jnp.int32),
            jnp.full((b,), top_p, jnp.float32),
            jnp.asarray([seed + i for i in range(b)], jnp.int32),
            jnp.full((b,), idx, jnp.int32))


# ---------------------------------------------------------------------------
# sample_tokens unit semantics
# ---------------------------------------------------------------------------

def test_temperature_zero_is_exact_argmax():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(6, 97)), jnp.float32)
    toks = sample_tokens(logits, *_lanes(6, temperature=0.0))
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.argmax(logits, axis=-1)))


def test_top_k_one_and_tiny_top_p_reduce_to_argmax():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(5, 97)), jnp.float32)
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    toks_k = sample_tokens(logits, *_lanes(5, temperature=1.3, top_k=1))
    np.testing.assert_array_equal(np.asarray(toks_k), greedy)
    # top_p=0 keeps only the head of the nucleus (rank 0 always survives)
    toks_p = sample_tokens(logits, *_lanes(5, temperature=0.9, top_p=0.0))
    np.testing.assert_array_equal(np.asarray(toks_p), greedy)


def test_top_k_truncation_restricts_support():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(1, 97)), jnp.float32)
    top5 = set(np.asarray(jnp.argsort(-logits[0]))[:5].tolist())
    seen = set()
    for idx in range(64):
        t = sample_tokens(logits, *_lanes(1, temperature=2.0, top_k=5,
                                          idx=idx))
        seen.add(int(t[0]))
    assert seen <= top5
    assert len(seen) > 1, "high temperature should spread over the top-k"


def test_same_seed_same_index_same_token_different_index_varies():
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(8, 97)), jnp.float32)
    a = sample_tokens(logits, *_lanes(8, temperature=1.0, seed=11, idx=4))
    b = sample_tokens(logits, *_lanes(8, temperature=1.0, seed=11, idx=4))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    c = sample_tokens(logits, *_lanes(8, temperature=1.0, seed=11, idx=5))
    assert np.any(np.asarray(a) != np.asarray(c)), \
        "advancing the sample index should change some draws"


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)
    assert SamplingParams().is_greedy
    assert not SamplingParams(temperature=0.5).is_greedy


# ---------------------------------------------------------------------------
# the all-greedy branch
# ---------------------------------------------------------------------------

_sample_jit = jax.jit(sample_tokens)


@pytest.mark.parametrize("vocab", [97, 151552])
@pytest.mark.parametrize("b", [1, 8, 32])
def test_all_greedy_batch_is_bitwise_argmax(b, vocab):
    """Every lane greedy: the dispatch returns ``argmax`` bit for bit,
    ties included (the lowest index wins)."""
    rng = np.random.default_rng(vocab + b)
    logits = rng.normal(size=(b, vocab)).astype(np.float32)
    logits[0, [3, vocab - 2]] = logits[0].max() + 1.0     # a tied maximum
    logits = jnp.asarray(logits)
    toks = _sample_jit(logits, *_lanes(b, temperature=0.0))
    assert toks.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.argmax(logits, axis=-1)))
    assert int(toks[0]) == 3


@pytest.mark.parametrize("idx", [0, 5])
def test_mixed_batch_matches_per_row_sampler(idx):
    """One lane samples (temperature 0.8, top-p 0.9), the rest are greedy:
    the whole batch takes the sampling path, token for token what
    ``_sample_row`` gives each row on its own."""
    b, vocab = 8, 151552
    rng = np.random.default_rng(8)
    logits = jnp.asarray(rng.normal(size=(b, vocab)) * 3.0, jnp.float32)
    temps, top_ks, top_ps, seeds, idxs = _lanes(b, temperature=0.0, idx=idx)
    temps = temps.at[2].set(0.8)
    top_ps = top_ps.at[2].set(0.9)
    toks = np.asarray(_sample_jit(logits, temps, top_ks, top_ps, seeds,
                                  idxs))
    row = jax.jit(_sample_row)
    ref = [int(row(logits[i], temps[i], top_ks[i], top_ps[i], seeds[i],
                   idxs[i])) for i in range(b)]
    np.testing.assert_array_equal(toks, ref)
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    np.testing.assert_array_equal(np.delete(toks, 2), np.delete(greedy, 2))


_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_CALLS = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation"
    r")=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")


def _computations(hlo_text):
    """HLO computation name -> its body lines."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and line.strip() != "}":
            cur.append(line)
    return comps


def _reachable(comps, root):
    """Body lines of ``root`` and of every computation it calls."""
    seen, todo, lines = set(), [root], []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            lines.append(line)
            for single, many in _CALLS.findall(line):
                todo += [single] if single else [
                    n.strip().lstrip("%") for n in many.split(",")]
    return lines


def test_compiled_sampler_branches_and_greedy_branch_has_no_sort():
    """The dispatch compiles to one ``conditional`` on a scalar; its
    greedy branch (branch 0: the predicate "any lane samples" is false)
    holds no sort, the sampling branch does."""
    b, vocab = 8, 1024
    logits = jax.ShapeDtypeStruct((b, vocab), jnp.float32)
    lanes = jax.eval_shape(lambda: _lanes(b))
    text = _sample_jit.lower(logits, *lanes).compile().as_text()
    cond = [line for line in text.splitlines() if " conditional(" in line]
    assert len(cond) == 1, cond
    many = re.search(r"branch_computations=\{([^}]*)\}", cond[0]).group(1)
    branches = [n.strip().lstrip("%") for n in many.split(",")]
    assert len(branches) == 2, cond[0]
    comps = _computations(text)
    greedy, sampled = (_reachable(comps, n) for n in branches)
    assert not any(" sort(" in line for line in greedy)
    assert any(" sort(" in line for line in sampled)
    # the sort carries the sorted values: no gather of B x V elements
    gathers = re.findall(r"= \w+\[([\d,]*)\]\S* gather\(", text)
    assert gathers, "the sampled token's index is a gather"
    assert all(np.prod([int(d) for d in dims.split(",") if d]) < b * vocab
               for dims in gathers), gathers


def _gather_row(logits, temp, top_k, top_p, seed, idx):
    """The plain formulation: argsort, then gather the logits in that
    order (the reference the sorted-values path must match bit for bit)."""
    vocab = logits.shape[-1]
    order = jnp.argsort(-logits)
    scaled = logits[order] / jnp.maximum(temp, 1e-6)
    keep = jnp.arange(vocab) < jnp.where(top_k <= 0, vocab, top_k)
    probs = jax.nn.softmax(scaled)
    keep &= (jnp.cumsum(probs) - probs) < top_p
    keep = keep.at[0].set(True)
    masked = jnp.where(keep, scaled, -jnp.inf)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), idx)
    return order[jax.random.categorical(key, masked)].astype(jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sampling_path_matches_gather_reference(dtype):
    """Every lane samples, over tied and untied logits: the tokens equal
    the argsort-and-gather formulation's, lane for lane."""
    b, vocab = 8, 4099
    rng = np.random.default_rng(10)
    x = rng.normal(size=(b, vocab)) * 3.0
    x[0, :40] = x[0].max()          # a tied head
    x[1] = np.round(x[1])           # ties everywhere
    logits = jnp.asarray(x, dtype)
    ref = jax.jit(jax.vmap(_gather_row))
    for temp, top_k, top_p in ((0.7, 0, 0.9), (1.3, 20, 1.0), (0.2, 0, 0.5)):
        lanes = _lanes(b, temperature=temp, top_k=top_k, top_p=top_p,
                       seed=11, idx=3)
        np.testing.assert_array_equal(
            np.asarray(_sample_jit(logits, *lanes)),
            np.asarray(ref(logits, *lanes)))


def test_engine_counts_greedy_sample_dispatches():
    """All-greedy traffic: every sampling dispatch (prefill pieces and
    decode steps) is an all-greedy one.  One sampled request among them
    makes at least one dispatch a sampling one."""
    cfg = _cfg()
    _, params = _params(cfg)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab, (n,)).tolist() for n in (7, 12, 5)]

    def serve(samplings):
        eng = ServeEngine(cfg, params, max_slots=2, max_seq=32,
                          prefill_chunk=8)
        eng.warmup()
        eng.reset_stats()
        for prompt, sp in zip(prompts, samplings):
            eng.submit(prompt, 4, sampling=sp)
        eng.run()
        return eng.stats

    greedy = serve([None] * 3)
    assert greedy["sample_dispatches"] >= (greedy["prefill_dispatches"]
                                           + greedy["decode_steps"])
    assert greedy["sample_greedy_dispatches"] == greedy["sample_dispatches"]
    mixed = serve([None, SamplingParams(temperature=0.7, seed=3), None])
    assert (mixed["sample_greedy_dispatches"]
            <= mixed["sample_dispatches"] - 1)


# ---------------------------------------------------------------------------
# engine-level determinism
# ---------------------------------------------------------------------------

def _run_engine(cfg, params, prompt, gen, sampling, **eng_kw):
    eng = ServeEngine(cfg, params, max_slots=2, max_seq=32,
                      prefill_chunk=8, **eng_kw)
    req = eng.submit(prompt, gen, sampling=sampling)
    eng.run()
    return req.generated


def test_sampled_tokens_identical_across_engine_restarts():
    """Same seed + same SamplingParams => identical tokens from a freshly
    rebuilt engine (the PRNG stream is a pure function of (seed, index))."""
    cfg = _cfg()
    _, params = _params(cfg)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab, (7,)).tolist()
    sp = SamplingParams(temperature=0.8, top_k=20, top_p=0.95, seed=123)
    first = _run_engine(cfg, params, prompt, 8, sp)
    second = _run_engine(cfg, params, prompt, 8, sp)
    assert first == second
    # a different seed changes the stream (same logits, same knobs)
    other = _run_engine(cfg, params, prompt, 8,
                        SamplingParams(temperature=0.8, top_k=20,
                                       top_p=0.95, seed=124))
    assert first != other


def test_greedy_sampling_params_bit_exact_with_default_engine():
    """temperature=0 through the sampling plumbing == the PR 2 greedy
    engine path (same argmax, token for token)."""
    cfg = _cfg()
    _, params = _params(cfg)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab, (9,)).tolist()
    explicit = _run_engine(cfg, params, prompt, 6, SamplingParams())
    default = _run_engine(cfg, params, prompt, 6, None)
    assert explicit == default


def test_sampled_stream_survives_eviction():
    """Eviction + re-admission re-prefills the generated tokens but must
    NOT resample them; the continuation keeps drawing from the same
    (seed, index) stream positions."""
    cfg = _cfg()
    _, params = _params(cfg)
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab, (6,)).tolist()
    sp = SamplingParams(temperature=0.9, seed=77)

    eng = ServeEngine(cfg, params, max_slots=1, max_seq=32, prefill_chunk=8)
    req = eng.submit(prompt, 6, sampling=sp)
    eng.step()
    eng.step()
    prefix_before = list(req.generated)
    eng.evict(0)
    eng.run()
    assert req.generated[:len(prefix_before)] == prefix_before

    uninterrupted = _run_engine(cfg, params, prompt, 6, sp)
    assert req.generated == uninterrupted


@pytest.mark.parametrize("arch_id", ["falcon-mamba-7b", "zamba2-1.2b"])
def test_recurrent_families_sample_deterministically(arch_id):
    """The sampling lanes ride the same decode dispatch for SSM/hybrid
    families (which have no prefix cache): restart-determinism holds."""
    cfg = _cfg(arch_id)
    _, params = _params(cfg)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab, (5,)).tolist()
    sp = SamplingParams(temperature=1.1, top_p=0.9, seed=9)
    assert (_run_engine(cfg, params, prompt, 5, sp)
            == _run_engine(cfg, params, prompt, 5, sp))
