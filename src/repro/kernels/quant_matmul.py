"""int8 x int8 -> int32 matmul with Theorem-planned K-blocking.

The MXU multiplies int8 tiles natively; the open question for a quantized
matmul is how many products may be reduced into an accumulator of a given
width before overflow — precisely the paper's carry-bits question. The block
size along K is chosen by :func:`repro.core.accum.plan_dot_accumulation`
(exact, from the Theorem); each block sums exactly, and block partials are
themselves multi-operand-added in a wider register (the "spill" plan).

With int32 accumulators and int8 inputs the exact block is 2^18 > any real K,
so the plan degenerates to one block (and the kernel is a plain tiled int
matmul). The plan becomes *binding* for narrow accumulators — e.g. the int16
emulation used in tests, where max_block = 2 — demonstrating that the bound
is exact: block+1 overflows, block does not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.accum import plan_dot_accumulation

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))

__all__ = ["quant_matmul_kernel", "quant_matmul_pallas"]


def quant_matmul_kernel(x_ref, w_ref, o_ref, *, acc_dtype, k_total, bk):
    """One (bm, bk) x (bk, bn) int8 tile product, accumulated into the
    revisited (bm, bn) int32 output tile. The K axis is masked against
    ``k_total`` (remainder blocks are padded with undefined values)."""
    k = pl.program_id(2)
    x = x_ref[...]
    if k_total % bk:
        offs = k * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        x = jnp.where(offs < k_total, x, jnp.zeros_like(x))
    prod = jnp.dot(x.astype(acc_dtype), w_ref[...].astype(acc_dtype),
                   preferred_element_type=acc_dtype)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = prod

    @pl.when(k != 0)
    def _accum():
        o_ref[...] = o_ref[...] + prod


@functools.partial(jax.jit, static_argnames=("bm", "bn", "acc_bits",
                                             "interpret"))
def quant_matmul_pallas(x: jnp.ndarray, w: jnp.ndarray, *, bm: int = 256,
                        bn: int = 256, acc_bits: int = 32,
                        interpret: bool = False) -> jnp.ndarray:
    """Exact integer matmul ``x @ w`` (int8 inputs, int32 result).

    K-blocking comes from the Theorem: bk <= max exactly-summable products
    for ``acc_bits``; bk is MXU-aligned (multiple of 128) when the bound
    allows. acc_bits < 32 uses an int32 carrier but asserts the plan keeps
    every partial within the emulated width (tests exploit this).
    """
    (m, k_total), (k2, n) = x.shape, w.shape
    assert k_total == k2, "inner dims must match"
    plan = plan_dot_accumulation(k_total, lhs_bits=8, rhs_bits=8,
                                 acc_bits=acc_bits, align=128)
    bk = min(plan.block, k_total)
    bm, bn = min(bm, m), min(bn, n)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k_total, bk))
    kernel = functools.partial(quant_matmul_kernel, acc_dtype=jnp.int32,
                               k_total=k_total, bk=bk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (m, n), jnp.int32, vma=jax.typeof(x).vma | jax.typeof(w).vma),
        compiler_params=_COMPILER_PARAMS if not interpret else None,
        interpret=interpret,
    )(x.astype(jnp.int8), w.astype(jnp.int8))
    return out
