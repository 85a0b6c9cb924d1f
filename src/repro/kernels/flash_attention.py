"""Causal GQA flash attention — Pallas TPU kernel.

The dominant memory-roofline term of every full-attention 32k cell is the
(B, H, S, S) score tensor round-tripping HBM (EXPERIMENTS.md §Perf). This
kernel never materializes it: K/V stream through VMEM in (block_k, head_dim)
tiles and the softmax runs in the streaming (m, l, acc) form — the same
partial-accumulator multi-operand combine the paper builds in gates, here
over VMEM tiles (and the same (m, l, o) triple the split-K decode psums
across the model axis).

Layout: the wrapper folds GQA groups into q rows — q: (B*Hkv, rep*S, hd),
k/v: (B*Hkv, S, hd) — one kernel serves any group size. S % block_q == 0
keeps blocks from straddling a group boundary, so the causal position of a
q row is ``row % S``.

Grid: (B*Hkv, q_blocks, k_blocks); the k axis is innermost/sequential and
carries (m, l, acc) in fp32 VMEM scratch. Blocks strictly above the causal
diagonal are skipped. MXU alignment: 128-row/col blocks; head_dim pads to
128 lanes in the wrapper.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))

__all__ = ["flash_attention_kernel", "flash_attention_pallas"]

NEG_INF = -1e30


def flash_attention_kernel(q_ref, k_ref, v_ref, o_ref,
                           m_scr, l_scr, acc_scr, *,
                           scale: float, block_q: int, block_k: int,
                           seq: int, causal: bool):
    j = pl.program_id(1)               # q block
    kk = pl.program_id(2)              # k block (sequential, carries state)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # q rows fold (rep, S): position within the sequence is row % seq
    q_row0 = j * block_q
    first_q_pos = q_row0 % seq
    live = (not causal) or (kk * block_k <= first_q_pos + block_q - 1)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = first_q_pos + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kk * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        acc_scr[...] = (acc_scr[...] * corr[:, None] +
                        jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(kk == nk - 1)
    def _final():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True, scale: float = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (B, S, Hq, hd); k/v: (B, S, Hkv, hd); Hq % Hkv == 0.

    Returns (B, S, Hq, hd) in q.dtype. S must divide by the block sizes
    (the wrapper shrinks blocks for short sequences).
    """
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    rep = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)

    hd_pad = (-hd) % 128
    if hd_pad:
        padw = ((0, 0), (0, 0), (0, 0), (0, hd_pad))
        q = jnp.pad(q, padw)
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
    hdp = hd + hd_pad

    # fold GQA groups into q rows: (B*Hkv, rep*S, hd) vs (B*Hkv, S, hd)
    q2 = q.transpose(0, 2, 1, 3).reshape(b, hkv, rep, s, hdp)
    q2 = q2.reshape(b * hkv, rep * s, hdp)
    k2 = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, hdp)
    v2 = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, hdp)

    grid = (b * hkv, rep * s // block_q, s // block_k)
    # under jax.shard_map the output varies over the axes q, k, v do
    vma = jax.typeof(q).vma | jax.typeof(k).vma | jax.typeof(v).vma
    kernel = functools.partial(
        flash_attention_kernel, scale=scale, block_q=block_q,
        block_k=block_k, seq=s, causal=causal)
    scratch = [pltpu.VMEM((block_q,), jnp.float32),
               pltpu.VMEM((block_q,), jnp.float32),
               pltpu.VMEM((block_q, hdp), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hdp), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_k, hdp), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((1, block_k, hdp), lambda i, j, kk: (i, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hdp), lambda i, j, kk: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(q2.shape, q.dtype, vma=vma),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS if not interpret else None,
    )(q2, k2, v2)

    out = out.reshape(b, hkv, rep, s, hdp).reshape(b, hq, s, hdp)
    out = out.transpose(0, 2, 1, 3)
    if hd_pad:
        out = out[..., :hd]
    return out
