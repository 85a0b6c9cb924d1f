"""Bit-plane LUT column adder — the paper's serial Algorithm-2 on the VPU.

This is the *faithful* kernel: each grid step processes a VMEM tile of B
independent N-operand additions (the "massively parallel environment" of
Lemma 3 — many small serial units side by side). For each of the M columns it

  1. extracts the column's bit plane from the packed int operands,
  2. runs the ones-count through the Fig-4 LUT netlist (XOR/AND gates — pure
     VPU bitwise ops, no multiplier involved),
  3. adds the carry buffer, emits the column bit, shifts the rest right,

exactly as §4's 4xM serial adder; the column loop is unrolled at trace time
(M is static), so the TPU sees a straight-line bitwise program. Carry-buffer
width is guaranteed by the Theorem (carry <= N-1), asserted at build time.

GPU-analogue note (DESIGN.md §2): the paper's RAM-LUT variant would need a
per-lane gather; the combinatorial variant used here maps to vector bitwise
ops, which is the TPU-idiomatic choice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import carry as carry_theory

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel",))

__all__ = ["bitplane_add_kernel", "bitplane_add_pallas"]


def _ones_count_gates(bits: jnp.ndarray) -> jnp.ndarray:
    """Hierarchical Fig-4 netlists over axis 0 (N operands): 4->3 units on
    groups of 4, partial counts summed — §3.3's hierarchical LUTs."""
    n = bits.shape[0]
    pad = (-n) % 4
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros((pad,) + bits.shape[1:], bits.dtype)], axis=0)
    g = bits.reshape((-1, 4) + bits.shape[1:])
    b0, b1, b2, b3 = g[:, 0], g[:, 1], g[:, 2], g[:, 3]
    s0, c0 = b0 ^ b1, b0 & b1
    s1, c1 = b2 ^ b3, b2 & b3
    z0, m = s0 ^ s1, s0 & s1
    t, z2p = c0 ^ c1, c0 & c1
    z1, kk = t ^ m, t & m
    z2 = z2p | kk
    counts = z0 + (z1 << 1) + (z2 << 2)     # (groups, ...) partial counts
    return jnp.sum(counts, axis=0)


def bitplane_add_kernel(x_ref, o_ref, *, m_bits: int):
    """x_ref: (N, bb) int32 tile — N operands for bb independent additions.
    o_ref: (bb,) int32 results."""
    x = x_ref[...]
    carry_buf = jnp.zeros(x.shape[1:], jnp.int32)
    result = jnp.zeros(x.shape[1:], jnp.int32)
    for i in range(m_bits):                     # one "clock" per column
        col = (x >> i) & 1                      # bit-plane extract
        lut_out = _ones_count_gates(col)        # Fig-4 gates
        total = lut_out + carry_buf
        result = result | ((total & 1) << i)    # emit column bit
        carry_buf = total >> 1                  # shift rest into carry buffer
    o_ref[...] = result + (carry_buf << m_bits)  # final drain clock


@functools.partial(jax.jit, static_argnames=("m_bits", "bb", "interpret"))
def bitplane_add_pallas(x: jnp.ndarray, *, m_bits: int, bb: int = 1024,
                        interpret: bool = False) -> jnp.ndarray:
    """Sum N packed-integer operands per lane, bit-serially via the LUT.

    Args:
      x: (N, B) int32 with each value < 2**m_bits; B independent additions.
      m_bits: word width M (static; the column loop unrolls M times).
      bb: lanes per grid step.
    Returns:
      (B,) int32 exact sums (width M + ceil(log2 N) <= 31 enforced).
    """
    n, batch = x.shape
    need = carry_theory.result_digits(n, m_bits, 2)
    if need > 31:
        raise ValueError(
            f"N={n}, M={m_bits} needs {need} result bits > int32 capacity")
    bb = min(bb, batch)
    grid = (pl.cdiv(batch, bb),)
    kernel = functools.partial(bitplane_add_kernel, m_bits=m_bits)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((n, bb), lambda i: (0, i))],
        out_specs=pl.BlockSpec((bb,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((batch,), jnp.int32,
                                       vma=jax.typeof(x).vma),
        compiler_params=_COMPILER_PARAMS if not interpret else None,
        interpret=interpret,
    )(x.astype(jnp.int32))
