"""Compile the serve path for a described TPU v5e chip (none attached).

The TPU compiler runs here against a ``v5e:2x2`` topology description:
it refuses what the chip would refuse (tile alignment, VMEM overuse, a
program that does not fit HBM) without spending chip time.  Covered: the
``moa_reduce`` split-K combine at llama3.2-3b decode shapes, the flash
attention kernel at a published-width prefill shape, the engine's
decode and verify dispatches at published widths with depth cut to two
layers (llama3.2-3b, and GLM-4-9B's 16-query-head groups, whose KV
view must never be repeated), and the sampler's all-greedy branch at
published vocabularies.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest-xdist
worker imports this file.  All such compiles stay in this one file.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels import ops as kops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moa_reduce import moa_reduce_pallas
from repro.models.common import init_params, shape_structs
from repro.models.registry import get_api
from repro.serve import EngineConfig, ServeEngine, cache, sample_tokens

#: HBM of one v5e chip (Google Cloud, "TPU v5e": 16 GB).
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU library otherwise writes its logs under the system temp dir
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, structs, sharding):
    placed = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        structs)
    return jax.jit(fn).lower(*placed).compile()


def _fits(compiled) -> bool:
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    return peak <= V5E_HBM_BYTES


# (pages, rows, cols) operands of the split-K page combine at llama3.2-3b
# widths (24 q heads x 128 head dim, 8 slots): the l and o partials of a
# decode step and of a K=4 verify step over 16 pages (max_seq 2048), a
# 7-page cache (a page count no multiple of 4, so the radix-4 tree pads
# inside the kernel), a one-slot prefill chunk, and a 20-operand stack
# the kernel splits into blocks of 16 (the masked remainder block).
@pytest.mark.parametrize("shape", [
    (16, 8, 24), (16, 8, 3072), (16, 8, 15360), (7, 8, 3072),
    (16, 1, 24 * 64 * 128), (20, 256, 512),
])
def test_moa_reduce_compiles_for_v5e(one_chip, shape):
    c = _compile(moa_reduce_pallas,
                 (jax.ShapeDtypeStruct(shape, jnp.float32),), one_chip)
    assert "tpu_custom_call" in c.as_text()
    assert _fits(c)


def test_flash_attention_compiles_for_v5e(one_chip):
    """A 1024-token prefill at llama3.2-3b widths: 24 query heads over 8
    KV heads of 128, bf16."""
    q = jax.ShapeDtypeStruct((1, 1024, 24, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 1024, 8, 128), jnp.bfloat16)
    c = _compile(lambda q, k, v: flash_attention_pallas(q, k, v),
                 (q, kv, kv), one_chip)
    assert "tpu_custom_call" in c.as_text()
    assert _fits(c)


def _arrays_of(compiled, slots, n_elements):
    """Instructions of ``compiled`` (fused ones included) whose output, or
    an element of its tuple, is an array of ``n_elements`` elements with an
    axis of ``slots`` (a per-slot array, not a weight of the same size)."""
    hits = []
    for line in compiled.as_text().splitlines():
        m = re.search(r" = (.*?) [a-z][\w.-]*\(", line)
        if m is None:
            continue
        for dims in re.findall(r"[a-z]\w*\[([\d,]+)\]", m.group(1)):
            dims = [int(d) for d in dims.split(",")]
            if slots in dims and math.prod(dims) == n_elements:
                hits.append(line.strip())
    return hits


# (config, slots, page, dispatch): llama3.2-3b's plain-decode and K=4
# verify executables, and GLM-4-9B's decode as the chat cell runs it
# (32 query heads over 2 KV heads, 32 slots, 128-token pages).
@pytest.mark.parametrize("arch_id,slots,page,dispatch", [
    pytest.param("llama3.2-3b", 8, None, "decode", id="decode"),
    pytest.param("llama3.2-3b", 8, None, "spec", id="spec"),
    pytest.param("glm4-9b", 32, 128, "decode", id="glm4-9b-decode"),
])
def test_engine_dispatch_compiles_for_v5e(one_chip, monkeypatch, arch_id,
                                          slots, page, dispatch):
    """The engine's decode (and verify) executables at published widths,
    two layers deep, ``slots`` x 2048 tokens of paged KV: the split-K
    combine must lower to the Pallas kernel, the program must fit one
    chip, and no instruction may hold the KV view repeated to the
    query-head count (the split-K core groups the queries per KV head)."""
    cfg = dataclasses.replace(get_config(arch_id), n_layers=2)
    specs = get_api(cfg).param_specs(cfg)
    params = jax.eval_shape(
        lambda k: init_params(specs, k, dtype=cfg.dtype), jax.random.key(0))
    # the kernel layer asks the default backend (the CPU here) whether it
    # is on a TPU; the compile below targets one
    monkeypatch.setattr(kops, "on_tpu", lambda: True)
    monkeypatch.setattr(cache, "state_zeros", shape_structs)
    eng = ServeEngine(cfg, params, config=EngineConfig(
        max_slots=slots, max_seq=2048, prefill_chunk=512, page_size=page,
        spec_k=4))
    assert eng.paged
    monkeypatch.setattr(
        eng, "_get", lambda key, fn, *structs: _compile(fn, structs,
                                                        one_chip))
    c = eng._decode_exe() if dispatch == "decode" else eng._spec_exe()
    assert "tpu_custom_call" in c.as_text()
    assert _fits(c)
    # repeating the K and V views to the query-head count would hold
    # (slots, 2048, n_heads, hd) arrays; the grouped contraction holds none
    assert _arrays_of(c, slots, slots * 2048 * cfg.n_heads * cfg.hd) == []


@pytest.mark.parametrize("vocab", [151552, 73448])
def test_sampler_keeps_its_greedy_branch_for_v5e(one_chip, vocab):
    """32 lanes over the GLM-4 and MiniCPM3 vocabularies: the chip's
    compiler keeps the one scalar ``conditional``, and the vocabulary
    sort sits only inside a branch, not in the entry computation that
    every dispatch runs."""
    b = 32
    lane = lambda dt: jax.ShapeDtypeStruct((b,), dt)
    c = _compile(sample_tokens,
                 (jax.ShapeDtypeStruct((b, vocab), jnp.float32),
                  lane(jnp.float32), lane(jnp.int32), lane(jnp.float32),
                  lane(jnp.int32), lane(jnp.int32)), one_chip)
    lines = c.as_text().splitlines()
    assert sum(" conditional(" in line for line in lines) == 1
    assert any(" sort(" in line for line in lines)
    start = next(i for i, line in enumerate(lines)
                 if line.startswith("ENTRY "))
    end = next(i for i in range(start, len(lines)) if lines[i] == "}")
    assert not any(" sort(" in line for line in lines[start:end])
