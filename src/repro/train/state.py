"""Train state: params + optimizer, with sharding derivation and the
pjit step builders (standard, serial-accumulated, pod-compressed)."""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist.collectives import make_tree_mesh
from repro.models.common import (ParamSpec, init_params, make_shardings,
                                 shape_structs)
from repro.models.registry import get_api
from repro.optim import compression
from repro.optim.adamw import (AdamWConfig, AdamWState, adamw_init,
                               adamw_update)
from repro.optim.grad_accum import accumulated_value_and_grad

__all__ = ["TrainState", "build_train_step", "train_state_specs",
           "train_state_shardings", "init_train_state"]


@dataclasses.dataclass
class TrainState:
    params: Any
    opt: AdamWState
    # pod-compressed mode only: per-pod error-feedback residuals
    err: Optional[Any] = None

    def as_tuple(self):
        return (self.params, self.opt) if self.err is None else (
            self.params, self.opt, self.err)


def train_state_specs(cfg: ModelConfig, pod_compressed: bool = False,
                      n_pods: int = 1) -> Dict[str, Any]:
    """ParamSpec trees for the full train state (used for both init and
    dry-run ShapeDtypeStructs)."""
    api = get_api(cfg)
    pspecs = api.param_specs(cfg)

    def opt_spec(s: ParamSpec) -> ParamSpec:
        return ParamSpec(s.shape, s.axes, dtype=jnp.float32, init="zeros")

    out = {
        "params": pspecs,
        "m": jax.tree.map(opt_spec, pspecs,
                          is_leaf=lambda x: isinstance(x, ParamSpec)),
        "v": jax.tree.map(opt_spec, pspecs,
                          is_leaf=lambda x: isinstance(x, ParamSpec)),
        "step": ParamSpec((), (), dtype=jnp.int32, init="zeros"),
    }
    if pod_compressed:
        def err_spec(s: ParamSpec) -> ParamSpec:
            # leading pod axis; inner axes keep the param's sharding, but the
            # fsdp axis indirection must avoid "pod" (it holds per-pod state)
            return ParamSpec((n_pods,) + s.shape, ("err_pod",) + s.axes,
                             dtype=jnp.float32, init="zeros")
        out["err"] = jax.tree.map(err_spec, pspecs,
                                  is_leaf=lambda x: isinstance(x, ParamSpec))
    return out


def train_state_shardings(cfg: ModelConfig, mesh: Mesh,
                          rules: Optional[Dict[str, Any]] = None,
                          pod_compressed: bool = False,
                          n_pods: int = 1) -> Dict[str, Any]:
    specs = train_state_specs(cfg, pod_compressed, n_pods)
    rules = dict(rules or {})
    from repro.models.common import DEFAULT_RULES
    base = dict(DEFAULT_RULES)
    base.update(rules)
    base["err_pod"] = "pod"
    if pod_compressed:
        # params replicated over pod (compressed DCN reduction needs full
        # per-pod copies); fsdp restricted to the in-pod data axis
        base["fsdp"] = ("data",)
        base["batch"] = ("pod", "data")
    return make_shardings(specs, mesh, base)


def init_train_state(cfg: ModelConfig, key, pod_compressed: bool = False,
                     n_pods: int = 1) -> Dict[str, Any]:
    # init the base state first so the per-param PRNG assignment is identical
    # with and without the compressed-mode "err" leaves (zeros, key-free)
    out = init_params(train_state_specs(cfg), key)
    if pod_compressed:
        full = train_state_specs(cfg, True, n_pods)
        out["err"] = init_params(full["err"], key)
    return out


def build_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                     mesh: Optional[Mesh] = None,
                     lr_schedule: Optional[Callable] = None,
                     grad_accum: int = 1,
                     pod_compressed: bool = False):
    """Return step(state_dict, batch) -> (state_dict, metrics).

    Modes:
      * standard pjit: gradients reduced automatically over DP axes.
      * grad_accum > 1: serial multi-operand accumulation over microbatches
        (stacked leading axis in the batch).
      * pod_compressed: manual-over-"pod" shard_map; int8 + exact integer
        radix-4 tree reduction at the pod (DCN) boundary, error feedback.
    """
    api = get_api(cfg)

    # NOTE (§Perf, refuted hypothesis): casting the fp32 master params to
    # bf16 ONCE at step entry — so ZeRO/TP gathers move bf16 — measured
    # WORSE on the 256-chip lowering (qwen train collective 230 -> 289
    # GB/dev): the optimizer consumes the fp32 tree anyway, so both copies
    # travel, and the convert-fed vocab shard_map re-triggers the XLA
    # partial-manual CHECK-crash (DESIGN.md §6b). Kept per-use casts.
    def loss_fn(params, batch):
        return api.train_loss(params, batch, cfg, mesh)

    if grad_accum > 1:
        vg = accumulated_value_and_grad(loss_fn, grad_accum)
    else:
        vg = jax.value_and_grad(loss_fn)

    def opt_apply(state, grads, loss):
        lr = lr_schedule(state["step"]) if lr_schedule else None
        opt = AdamWState(step=state["step"], m=state["m"], v=state["v"])
        params, opt, metrics = adamw_update(opt_cfg, state["params"], grads,
                                            opt, lr)
        metrics["loss"] = loss
        new_state = dict(state)
        new_state.update(params=params, m=opt.m, v=opt.v, step=opt.step)
        return new_state, metrics

    if not pod_compressed:
        def step(state, batch):
            loss, grads = vg(state["params"], batch)
            return opt_apply(state, grads, loss)
        return step

    # --- pod-compressed mode -------------------------------------------------
    assert mesh is not None and "pod" in mesh.shape
    n_pods = mesh.shape["pod"]
    tmesh, sub_axes = make_tree_mesh(mesh, "pod")

    # The manual-over-pod region cannot contain the manual TP kernels
    # (Shardy rejects nested sdy.manual_computation re-binding axes), so the
    # compressed path runs the model with *auto* TP — the in-pod "data" and
    # "model" axes stay Auto inside the region and constrain() still shards
    # the heavy matmuls. Semantics are identical; only the embed/EP
    # collective schedule differs (partitioner-chosen instead of manual).
    cfg_c = dataclasses.replace(cfg, use_tp_shardmap=False, use_ep=False)

    def loss_fn_c(params, batch):
        return api.train_loss(params, batch, cfg_c, mesh)

    vg_c = (accumulated_value_and_grad(loss_fn_c, grad_accum)
            if grad_accum > 1 else jax.value_and_grad(loss_fn_c))

    def step(state, batch):
        def per_pod(params, err, batch):
            # make params "varying over pod" so AD yields the PER-POD
            # partial gradient. Without it the transpose inserts an implicit
            # fp32 psum over the pod axis — the compressed reduction below
            # would then double-reduce (and the DCN bytes would already have
            # been spent).
            params = jax.tree.map(
                lambda p: jax.lax.pcast(p, tuple(sub_axes), to="varying"),
                params)
            err = jax.tree.map(lambda e: e[0], err)   # strip pod block axis
            loss, grads = vg_c(params, batch)
            grads, new_err = compression.compressed_psum_mean(
                grads, err, sub_axes, n_pods)
            loss = jax.lax.pmean(loss, sub_axes)
            new_err = jax.tree.map(lambda e: e[None], new_err)
            return loss, grads, new_err

        pod_first = P(sub_axes)
        loss, grads, new_err = jax.shard_map(
            per_pod,
            mesh=tmesh,
            axis_names=frozenset(sub_axes),
            in_specs=(P(), pod_first, pod_first),
            out_specs=(P(), P(), pod_first),
        )(state["params"], state["err"], batch)
        new_state, metrics = opt_apply(state, grads, loss)
        new_state["err"] = new_err
        return new_state, metrics

    return step
