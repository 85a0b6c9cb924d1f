"""Mixture-of-Experts FFN: EP all-to-all dispatch + dense fallback.

The expert-parallel path (``moe_ffn_ep``) is the production layout: experts
are sharded over the ``model`` mesh axis; each device routes its local tokens,
packs per-destination capacity buffers, exchanges them with a single
``all_to_all``, runs its local experts, and reverses the exchange. The top-k
weighted combine at the end is an explicit **multi-operand accumulation**
(k partial expert outputs per token) routed through the fused MOA reduce.

Capacity semantics: each source shard may send up to
``ceil(T_local * k * capacity_factor / E)`` tokens per expert; overflow
tokens are dropped (standard GShard behavior), which the load-balancing
auxiliary loss discourages.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models.common import ParamSpec, constrain, shardmap_mesh

__all__ = ["moe_param_specs", "moe_ffn", "dense_ffn", "dense_ffn_specs"]


def dense_ffn_specs(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w1": ParamSpec((d, f), ("embed", "mlp")),
        "w3": ParamSpec((d, f), ("embed", "mlp")),
        "w2": ParamSpec((f, d), ("mlp", "embed")),
    }


def moe_param_specs(cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    specs = {
        # router is expert-sharded (dim 1 -> model axis): inside the EP
        # shard_map every differentiable operand must be *varying* over the
        # manual axis — XLA's partial-manual transpose of a replicated
        # operand (implicit grad-psum) CHECK-crashes at 256 devices.
        "router": ParamSpec((d, e), ("embed", "experts"), scale=0.02),
        "w1": ParamSpec((e, d, f), ("experts", "embed", "moe_mlp")),
        "w3": ParamSpec((e, d, f), ("experts", "embed", "moe_mlp")),
        "w2": ParamSpec((e, f, d), ("experts", "moe_mlp", "embed")),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        specs["shared_w1"] = ParamSpec((d, fs), ("embed", "mlp"))
        specs["shared_w3"] = ParamSpec((d, fs), ("embed", "mlp"))
        specs["shared_w2"] = ParamSpec((fs, d), ("mlp", "embed"))
    return specs


def _topk_combine(gathered: jnp.ndarray, weights: jnp.ndarray,
                  t: int, d: int, cfg: ModelConfig) -> jnp.ndarray:
    """Weighted top-k expert combine — a k-operand accumulation per token.

    Routed through the fused multi-operand reduce (Pallas on TPU, jnp
    oracle elsewhere): one pass over the k partial outputs instead of k-1
    chained adds re-reading HBM (the paper's §1 motivation at tensor scale).
    """
    parts = gathered.reshape(t, cfg.top_k, d) * weights[..., None]
    if cfg.use_moa_reduce:
        return kops.moa_reduce(jnp.moveaxis(parts, 1, 0),
                               acc_dtype=jnp.float32,
                               out_dtype=gathered.dtype)
    return jnp.sum(parts, axis=1)


def dense_ffn(x: jnp.ndarray, p: dict, cfg: ModelConfig) -> jnp.ndarray:
    h = jax.nn.silu(x @ p["w1"].astype(x.dtype)) * (x @ p["w3"].astype(x.dtype))
    h = constrain(h, ("batch", None, "mlp"))
    return h @ p["w2"].astype(x.dtype)


def _router(xt: jnp.ndarray, router_w: jnp.ndarray, cfg: ModelConfig,
            gather_axis: Optional[str] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Return (weights (T,k), expert_idx (T,k), aux_loss scalar).

    ``gather_axis``: inside an EP shard_map, router_w is the LOCAL
    (d, e_local) expert-shard. The WEIGHT (tiny: d x E) is all-gathered
    before the matmul — tokens are seq-sharded per shard, so gathering
    logits would mix different token sets. The transpose of the gather is
    an explicit reduce-scatter, keeping every differentiable operand
    varying over the manual axis (see moe_param_specs note)."""
    if gather_axis is not None:
        router_w = jax.lax.all_gather(router_w, gather_axis, axis=-1,
                                      tiled=True)
    logits = (xt @ router_w.astype(xt.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.renorm_topk and cfg.top_k > 1:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch-style load-balancing loss: E * sum_e f_e * P_e
    e = cfg.n_experts
    f_e = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e) * cfg.router_aux_coef
    return weights.astype(xt.dtype), idx, aux


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    return max(1, math.ceil(tokens * cfg.top_k * cfg.capacity_factor
                            / cfg.n_experts))


def _dispatch_indices(idx: jnp.ndarray, e: int, cap: int):
    """Queue position of each (token, k) assignment within its expert;
    entries past capacity are flagged. idx: (T, k) -> (pos (T*k,), keep)."""
    flat_e = idx.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1          # position within expert
    pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = pos < cap
    return flat_e, jnp.where(keep, pos, cap - 1), keep


def _local_expert_ffn(tokens: jnp.ndarray, w1, w3, w2, dtype) -> jnp.ndarray:
    """tokens: (E_local, C_total, D) -> same shape through each expert."""
    h = jnp.einsum("ecd,edf->ecf", tokens, w1.astype(dtype))
    g = jnp.einsum("ecd,edf->ecf", tokens, w3.astype(dtype))
    h = jax.nn.silu(h) * g
    return jnp.einsum("ecf,efd->ecd", h, w2.astype(dtype))


def moe_ffn_dense_dispatch(x: jnp.ndarray, p: dict, cfg: ModelConfig
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Reference path (no collectives): capacity-buffered dispatch on the
    full token set. Used on small meshes/CPU and as the EP oracle."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    weights, idx, aux = _router(xt, p["router"], cfg)
    cap = _capacity(t, cfg)
    e = cfg.n_experts
    flat_e, pos, keep = _dispatch_indices(idx, e, cap)
    xk = jnp.repeat(xt, cfg.top_k, axis=0)            # (T*k, D)
    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[flat_e, pos].add(xk * keep[:, None].astype(x.dtype))
    out_buf = _local_expert_ffn(buf, p["w1"], p["w3"], p["w2"], x.dtype)
    gathered = out_buf[flat_e, pos] * keep[:, None].astype(x.dtype)
    # top-k weighted combine: a k-operand accumulation per token
    combined = _topk_combine(gathered, weights, t, d, cfg)
    return combined.reshape(b, s, d), aux


def moe_ffn_ep(x: jnp.ndarray, p: dict, cfg: ModelConfig, mesh: Mesh,
               ep_axis: str = "model") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel path: FULL-manual shard_map over every mesh axis.

    batch is manual over the DP axes and seq over ``ep_axis`` (SP reuse),
    so the capacity buffer is sized on the PER-DEVICE token count. (The
    earlier partial-manual form saw the global batch inside the region and
    sized the all-to-all 16x too big on the production mesh — found by the
    §Perf roofline loop.) Expert weights arrive fsdp-sharded and are
    all-gathered over the DP axes in-region (ZeRO-3; the gather transposes
    to a bandwidth-optimal reduce-scatter for the gradients).
    """
    ep = mesh.shape[ep_axis]
    e = cfg.n_experts
    assert e % ep == 0, (e, ep)
    e_local = e // ep
    dp_axes = tuple(a for a in mesh.axis_names if a != ep_axis)

    def local_fn(x_loc, router_w, w1, w3, w2):
        if dp_axes:
            # in-region FSDP: gather the embed dim of the expert weights
            router_w = jax.lax.all_gather(router_w, dp_axes, axis=0,
                                          tiled=True)
            w1 = jax.lax.all_gather(w1, dp_axes, axis=1, tiled=True)
            w3 = jax.lax.all_gather(w3, dp_axes, axis=1, tiled=True)
            w2 = jax.lax.all_gather(w2, dp_axes, axis=2, tiled=True)
        bl, sl, d = x_loc.shape
        xt = x_loc.reshape(-1, d)
        t = xt.shape[0]                        # per-device tokens
        weights, idx, aux = _router(xt, router_w, cfg, gather_axis=ep_axis)
        cap = _capacity(t, cfg)
        flat_e, pos, keep = _dispatch_indices(idx, e, cap)
        xk = jnp.repeat(xt, cfg.top_k, axis=0)
        send = jnp.zeros((e, cap, d), x_loc.dtype)
        send = send.at[flat_e, pos].add(xk * keep[:, None].astype(x_loc.dtype))
        # (E, cap, D) -> (ep, e_local*cap, D) -> exchange -> per-source rows
        send = send.reshape(ep, e_local * cap, d)
        recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=0,
                                  tiled=False)
        # recv: (ep, e_local*cap, D); axis 0 = source shard
        tokens = recv.reshape(ep, e_local, cap, d)
        tokens = jnp.moveaxis(tokens, 1, 0).reshape(e_local, ep * cap, d)
        out = _local_expert_ffn(tokens, w1, w3, w2, x_loc.dtype)
        out = jnp.moveaxis(out.reshape(e_local, ep, cap, d), 0, 1)
        back = jax.lax.all_to_all(out.reshape(ep, e_local * cap, d), ep_axis,
                                  split_axis=0, concat_axis=0, tiled=False)
        out_buf = back.reshape(e, cap, d)
        gathered = out_buf[flat_e, pos] * keep[:, None].astype(x_loc.dtype)
        combined = _topk_combine(gathered, weights, t, d, cfg)
        aux = jax.lax.pmean(aux, (ep_axis,) + dp_axes)
        return combined.reshape(bl, sl, d), aux

    batch_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes
                                                   else None)
    out = jax.shard_map(
        local_fn, mesh=shardmap_mesh(mesh),
        axis_names=frozenset(mesh.axis_names),
        in_specs=(P(batch_spec, ep_axis, None), P(batch_spec, ep_axis),
                  P(ep_axis, batch_spec, None), P(ep_axis, batch_spec, None),
                  P(ep_axis, None, batch_spec)),
        out_specs=(P(batch_spec, ep_axis, None), P()),
    )(x, p["router"], p["w1"], p["w3"], p["w2"])
    return out


def moe_ffn_ep_psum(x: jnp.ndarray, p: dict, cfg: ModelConfig, mesh: Mesh,
                    ep_axis: str = "model") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Decode-shape EP: tokens replicated over the expert axis, each shard
    computes only the tokens routed to ITS experts, partial outputs psum'd —
    the combine across expert shards is a multi-operand reduction over the
    model axis (radix-decomposable, see dist.collectives)."""
    ep = mesh.shape[ep_axis]
    e = cfg.n_experts
    e_local = e // ep

    def local_fn(x_loc, router_w, w1, w3, w2):
        # type the replicated tokens as varying over the expert axis.
        # XLA's partial-manual partitioner CHECK-crashes (CreateBinary on a
        # copy) when a replicated operand feeds this region at 256 devices;
        # with every operand varying it takes the well-tested path.
        x_loc = jax.lax.pcast(x_loc, ep_axis, to="varying")
        bl, sl, d = x_loc.shape
        xt = x_loc.reshape(-1, d)
        t = xt.shape[0]
        weights, idx, aux = _router(xt, router_w, cfg, gather_axis=ep_axis)
        shard = jax.lax.axis_index(ep_axis)
        lo = shard * e_local
        cap = _capacity(t, cfg)
        flat_e, pos, keep = _dispatch_indices(idx, e, cap)
        mine = (flat_e >= lo) & (flat_e < lo + e_local) & keep
        local_idx = jnp.clip(flat_e - lo, 0, e_local - 1)
        xk = jnp.repeat(xt, cfg.top_k, axis=0)
        buf = jnp.zeros((e_local, cap, d), x_loc.dtype)
        buf = buf.at[local_idx, pos].add(xk * mine[:, None].astype(x_loc.dtype))
        out_buf = _local_expert_ffn(buf, w1, w3, w2, x_loc.dtype)
        gathered = out_buf[local_idx, pos] * mine[:, None].astype(x_loc.dtype)
        partial = _topk_combine(gathered, weights, t, d, cfg)
        y = jax.lax.psum(partial, ep_axis)
        # tokens are replicated over ep_axis here, so aux is identical on
        # every shard — the pmean only discharges the varying-axes type
        aux = jax.lax.pmean(aux, ep_axis)
        return y.reshape(bl, sl, d), aux

    return jax.shard_map(
        local_fn, mesh=shardmap_mesh(mesh), axis_names=frozenset({ep_axis}),
        in_specs=(P(), P(None, ep_axis), P(ep_axis, None, None),
                  P(ep_axis, None, None), P(ep_axis, None, None)),
        out_specs=(P(), P()),
    )(x, p["router"], p["w1"], p["w3"], p["w2"])


def moe_ffn(x: jnp.ndarray, p: dict, cfg: ModelConfig,
            mesh: Optional[Mesh] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE FFN with optional shared experts (Llama-4 style)."""
    ep_ok = (cfg.use_ep and mesh is not None and not mesh.empty
             and "model" in mesh.shape and mesh.shape["model"] > 1
             and cfg.n_experts % mesh.shape["model"] == 0)
    if ep_ok:
        dp = 1
        for a in mesh.axis_names:
            if a != "model":
                dp *= mesh.shape[a]
        ep_ok = x.shape[0] % dp == 0
    seq_shardable = ep_ok and x.shape[1] % mesh.shape["model"] == 0
    if ep_ok and seq_shardable:
        y, aux = moe_ffn_ep(x, p, cfg, mesh)
    else:
        # decode/unshardable-seq: auto-sharded dense dispatch. The manual
        # ep_psum variant (kept + tested at small scale) trips an XLA
        # partial-manual partitioner CHECK at 256 devices on replicated
        # token operands ("Invalid binary instruction opcode copy"); the
        # partitioner derives the same expert-sharded compute from the
        # one-hot dispatch einsum here.
        y, aux = moe_ffn_dense_dispatch(x, p, cfg)
    if cfg.n_shared_experts:
        h = jax.nn.silu(x @ p["shared_w1"].astype(x.dtype)) * (
            x @ p["shared_w3"].astype(x.dtype))
        y = y + h @ p["shared_w2"].astype(x.dtype)
    return y, aux
