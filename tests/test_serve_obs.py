"""The serve engine's own observability: phase spans on the profiler's
clock, the phase and first-token counters, the request timestamps, and the
named scopes and names of the compiled executables.

Paged engines of a GQA (GLM-4) and an MLA (MiniCPM3) family at reduced
widths serve a few requests; one of them retires on its first token.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config
from repro.models.common import init_params
from repro.models.registry import get_api
from repro.serve import ServeEngine

jax.config.update("jax_enable_x64", False)

ARCHS = ["glm4-9b", "minicpm3-4b"]          # GQA + MLA families
#: (prompt length, max_new) per request; the last retires at prefill
SHAPES = [(20, 5), (9, 7), (30, 4), (13, 1)]
SPANS = {"serve.step", "serve.admit", "serve.admit.plan",
         "serve.admit.prefill", "serve.admit.commit", "serve.decode",
         "serve.decode.prepare", "serve.decode.device",
         "serve.decode.commit"}
COUNTERS = ("admit_plan_s", "admit_commit_s", "decode_prepare_s",
            "decode_commit_s", "first_token_hold_s", "first_tokens",
            "prefill_context_tokens", "decode_context_tokens")
#: ``%name = ... metadata={op_name="jit(decode)/sample/..."`` in HLO text
_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name="([^"]+)"')


def _engine(arch, **knobs):
    cfg = get_config(arch).reduced(dtype=jnp.float32)
    api = get_api(cfg)
    params = init_params(api.param_specs(cfg), jax.random.key(0))
    kw = dict(max_slots=2, max_seq=64, prefill_chunk=16, page_size=8,
              paged_kv=True, prefix_cache=False, spec_k=0)
    kw.update(knobs)
    return ServeEngine(cfg, params, **kw)


def _submit(eng, shapes=SHAPES):
    return [eng.submit([(7 * i + j) % eng.cfg.vocab for j in range(p)], n)
            for i, (p, n) in enumerate(shapes)]


@pytest.fixture(scope="module", params=ARCHS)
def served(request):
    """An engine that has served ``SHAPES`` to the end (2 slots, so the
    requests queue and admissions interleave with decode steps)."""
    eng = _engine(request.param)
    eng.warmup()
    reqs = _submit(eng)
    eng.run()
    return eng, reqs


def scopes(hlo_text: str):
    """Instruction name -> its top-level named scope (the first part of
    ``op_name`` after the ``jit(...)`` prefixes)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            parts = [p for p in m.group(2).split("/")
                     if not p.startswith("jit(")]
            if parts:
                out[m.group(1)] = parts[0]
    return out


def test_every_new_counter_counts(served):
    eng, reqs = served
    assert all(r.done for r in reqs)
    for name in COUNTERS:
        assert eng.stats[name] > 0, name
    assert eng.stats["first_tokens"] == len(reqs)
    assert eng.stats["first_tokens"] <= eng.stats["admissions"]


def test_context_counters_equal_the_keys_attended(served):
    """Prefill row i attends i + 1 keys; each decode step of a request at
    position p attends p + 1 (no prefix reuse here)."""
    eng, reqs = served
    prefill = sum(p * (p + 1) // 2 for p, _ in SHAPES)
    decode = sum(sum(range(p + 1, p + n)) for p, n in SHAPES)
    assert eng.stats["prefill_context_tokens"] == prefill
    assert eng.stats["decode_context_tokens"] == decode


def test_request_timestamps_are_ordered(served):
    _, reqs = served
    for r in reqs:
        assert r.submit_t <= r.admit_t <= r.first_token_t <= r.finish_t


@pytest.mark.parametrize("mode", ["chain", "tree"])
def test_spec_paths_count_their_phases(mode):
    eng = _engine("glm4-9b", spec_k=3, spec_mode=mode, spec_tree_nodes=4,
                  spec_branch=2)
    reqs = _submit(eng)
    eng.run()
    assert eng.stats["spec_steps"] > 0
    for name in COUNTERS:
        assert eng.stats[name] > 0, (mode, name)
    assert eng.stats["first_tokens"] == len(reqs)
    for r in reqs:
        assert r.submit_t <= r.admit_t <= r.first_token_t <= r.finish_t


def test_a_profiler_session_records_every_serve_span(tmp_path):
    from jax.profiler import ProfileData

    eng = _engine("glm4-9b")
    eng.warmup()
    _submit(eng, SHAPES[:2])
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**",
                                         "*.xplane.pb"), recursive=True))[-1]
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("serve.")}
    assert names == SPANS


def test_compiled_decode_maps_instructions_to_model_and_sample(served):
    eng, _ = served
    text = eng._exe["decode"].as_text()
    assert text.startswith("HloModule jit_decode,")
    found = set(scopes(text).values())
    assert {"model", "sample"} <= found, found


def test_prefill_buckets_are_named_by_their_key(served):
    eng, _ = served
    for cb in eng.chunk_buckets:
        text = eng._exe[("prefill", cb)].as_text()
        assert text.startswith(f"HloModule jit_prefill_b{cb},")
        assert {"model", "sample"} <= set(scopes(text).values())


def test_reset_stats_zeroes_the_new_counters(served):
    eng, _ = served
    eng.reset_stats()
    assert all(eng.stats[name] == 0 for name in COUNTERS)
