"""Fused N-operand reduction kernel (TPU adaptation of the Fig-7 adder).

A chained implementation of ``x1 + x2 + ... + xN`` emits N-1 two-operand HLO
adds, each streaming its inputs from HBM — the exact inefficiency the paper
attributes to "conventional two operand adders" (§1). This kernel is the
combinatorial multi-operand adder rethought for the TPU memory hierarchy:
every grid step loads one VMEM tile of *all* (or a block of) operands and
reduces them on-core in a radix-4 unrolled tree (§7's reconfiguration tree in
registers), writing each output tile once.

Memory traffic: chained adds move (2N-2) x tile reads + (N-1) x tile writes;
the fused kernel moves N reads + 1 write — a (3N-3)/(N+1) ~ 3x traffic cut
for large N, which is what matters for this bandwidth-bound op.

Grid: (rows/bm, cols/bn, N/bk) with the operand axis innermost ("arbitrary"
semantics) so partial sums accumulate in the revisited output tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import repro.dist.plan as dist_plan

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))

__all__ = ["moa_reduce_kernel", "moa_reduce_pallas", "radix4_tree_sum"]


def radix4_tree_sum(x: jnp.ndarray,
                     plan: "dist_plan.ReductionPlan | None" = None) -> jnp.ndarray:
    """Radix-4 tree reduction over axis 0 (the §7 tree, in registers).

    Levels (padding + grouping) come from the shared
    :class:`repro.dist.plan.ReductionPlan` — the same plan that shapes
    :func:`repro.core.moa.reconfigured_add` and the mesh collectives.

    Tree reduction also improves fp numerics vs left-to-right chaining:
    error grows O(log N) instead of O(N).
    """
    plan = plan or dist_plan.make_reduction_plan(x.shape[0])
    if plan.radix != 4:
        raise ValueError(f"the unrolled 4-operand add below requires a "
                         f"radix-4 plan, got radix={plan.radix}")
    if plan.n != x.shape[0]:
        raise ValueError(f"plan is for N={plan.n}, got {x.shape[0]} operands")
    for level in plan.levels:
        if level.pad:
            pad = jnp.zeros((level.pad,) + x.shape[1:], x.dtype)
            x = jnp.concatenate([x, pad], axis=0)
        g = x.reshape((level.groups, plan.radix) + x.shape[1:])
        # one "4-operand adder" per group: two levels of pairwise adds
        x = (g[:, 0] + g[:, 1]) + (g[:, 2] + g[:, 3])
    return x[0]


def moa_reduce_kernel(x_ref, o_ref, *, acc_dtype, n_total, bk):
    """Pallas kernel body: x_ref is a (bk, bm, bn) VMEM tile of operands,
    o_ref the (bm, bn) output tile (revisited across the operand grid axis).

    The operand axis is masked against ``n_total``: remainder blocks are
    padded by Pallas with undefined values which must not enter the sum.
    """
    k = pl.program_id(2)
    x = x_ref[...]
    if n_total % bk:
        offs = k * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1, 1), 0)
        x = jnp.where(offs < n_total, x, jnp.zeros_like(x))
    partial = radix4_tree_sum(x.astype(acc_dtype),
                               dist_plan.make_reduction_plan(bk))

    @pl.when(k == 0)
    def _init():
        o_ref[...] = partial

    @pl.when(k != 0)
    def _accum():
        o_ref[...] = o_ref[...] + partial


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "acc_dtype",
                                             "out_dtype", "interpret"))
def moa_reduce_pallas(x: jnp.ndarray, *, bm: int = 256, bn: int = 256,
                      bk: int | None = None, acc_dtype=jnp.float32,
                      out_dtype=None, interpret: bool = False) -> jnp.ndarray:
    """Sum ``x`` of shape (N, rows, cols) over axis 0 in a single fused pass.

    Args:
      x: stacked operands (N, rows, cols). rows/cols need not be multiples of
        the block — Pallas masks the remainder tiles.
      bm/bn: VMEM tile of the output. 256x256xfp32 = 256 KiB/operand-block.
      bk: operands per grid step (defaults to all of N if it fits ~VMEM
        budget, else 8). Accumulation across bk-steps stays in the output
        tile (int: exact by the Theorem's width plan; float: fp32).
      acc_dtype: accumulator dtype (fp32 for floats; int32 for ints).
      out_dtype: output dtype (defaults to input dtype).
    """
    n, rows, cols = x.shape
    out_dtype = out_dtype or x.dtype
    if bk is None:
        # VMEM budget heuristic: keep the operand tile under ~4 MiB.
        per_op = bm * bn * x.dtype.itemsize
        bk = max(1, min(n, (4 * 1024 * 1024) // per_op))
    bm = min(bm, rows)
    bn = min(bn, cols)
    bk = min(bk, n)
    grid = (pl.cdiv(rows, bm), pl.cdiv(cols, bn), pl.cdiv(n, bk))
    kernel = functools.partial(moa_reduce_kernel, acc_dtype=acc_dtype,
                               n_total=n, bk=bk)
    acc = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bk, bm, bn), lambda i, j, k: (k, i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        # under jax.shard_map (the sharded serve dispatches) the sum varies
        # over the mesh axes its operands vary over
        out_shape=jax.ShapeDtypeStruct((rows, cols), acc_dtype,
                                       vma=jax.typeof(x).vma),
        compiler_params=_COMPILER_PARAMS if not interpret else None,
        interpret=interpret,
    )(x)
    return acc.astype(out_dtype)


# Back-compat alias: pre-serve-engine callers imported the private name.
_radix4_tree_sum = radix4_tree_sum
