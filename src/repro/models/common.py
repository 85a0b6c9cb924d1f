"""Model substrate: param-spec system, logical-axis sharding, shared layers.

Every model declares its parameters as a pytree of :class:`ParamSpec`
(shape + dtype + logical axis names). From that single declaration we derive:

* ``init_params``      — PRNG initialization (fan-in scaled normal / zeros),
* ``shape_structs``    — ShapeDtypeStruct tree for AOT dry-run lowering,
* ``make_shardings``   — NamedSharding tree via logical->mesh axis rules,
  with automatic divisibility fallback (e.g. kv_heads=2 cannot shard over a
  16-way model axis -> replicated).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math
from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P
from jax._src import mesh as _jax_mesh

__all__ = [
    "ParamSpec", "init_params", "shape_structs", "make_shardings",
    "logical_to_pspec", "constrain", "DEFAULT_RULES",
    "rms_norm", "rope_angles", "apply_rope", "cross_entropy_loss",
    "param_count", "scan", "unrolled_scans", "manual_axis_sizes",
    "match_vma",
]

# ---------------------------------------------------------------------------
# scan with a cost-fidelity escape hatch
# ---------------------------------------------------------------------------
# XLA's HloCostAnalysis visits a while-loop body ONCE, so FLOPs/bytes of
# scan-over-layers models are undercounted by the trip count. All model
# scans go through this wrapper; the dry-run's cost pass re-lowers inside
# ``unrolled_scans()`` to get trip-complete numbers, while production
# lowering keeps the O(1)-in-depth HLO.

_SCAN_UNROLL = contextvars.ContextVar("repro_scan_unroll", default=False)


@contextlib.contextmanager
def unrolled_scans():
    tok = _SCAN_UNROLL.set(True)
    try:
        yield
    finally:
        _SCAN_UNROLL.reset(tok)


def scan(f, init, xs=None, length=None, **kw):
    if _SCAN_UNROLL.get():
        kw = dict(kw)
        kw["unroll"] = True
    return jax.lax.scan(f, init, xs, length=length, **kw)


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declarative parameter: shape, dtype, logical axes, init scale."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: Any = jnp.float32
    init: str = "normal"        # "normal" | "zeros" | "ones" | "ssm_dt" | "ssm_a"
    scale: Optional[float] = None  # override fan-in scale

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _init_leaf(spec: ParamSpec, key) -> jnp.ndarray:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "ssm_a":
        # Mamba A init: -[1..state] broadcast, stored as log
        state = spec.shape[-1]
        a = jnp.tile(jnp.arange(1, state + 1, dtype=jnp.float32),
                     spec.shape[:-1] + (1,)).reshape(spec.shape)
        return jnp.log(a).astype(spec.dtype)
    if spec.init == "ssm_dt":
        # dt bias ~ log-uniform in [1e-3, 1e-1]
        u = jax.random.uniform(key, spec.shape, jnp.float32,
                               minval=math.log(1e-3), maxval=math.log(1e-1))
        return jnp.exp(u).astype(spec.dtype)
    scale = spec.scale
    if scale is None:
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        scale = 1.0 / math.sqrt(max(1, fan_in))
    return (jax.random.normal(key, spec.shape, jnp.float32) * scale
            ).astype(spec.dtype)


def init_params(specs, key, dtype=None) -> Any:
    """Initialize a full param pytree from a ParamSpec tree.

    ``dtype`` stores every floating leaf in that type instead of its
    spec's (the serve path keeps weights in the compute dtype, which the
    layers cast them to at every use; bf16 halves the bytes)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    if dtype is not None:
        leaves = [dataclasses.replace(s, dtype=dtype)
                  if jnp.issubdtype(s.dtype, jnp.floating) else s
                  for s in leaves]
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(s, k) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def shape_structs(specs) -> Any:
    """ShapeDtypeStruct tree (no allocation) for AOT lowering."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs,
        is_leaf=_is_spec)


#: Default logical-axis -> mesh-axis rules (see DESIGN.md §5).
DEFAULT_RULES: Dict[str, Any] = {
    "batch": ("pod", "data"),
    "fsdp": ("pod", "data"),       # ZeRO-3 weight shard axis
    "embed": "fsdp",               # indirection: embed dims shard via fsdp
    "vocab": "model",
    "q_heads": "model",
    "kv_heads": "model",
    "heads": "model",
    "mlp": "model",
    "experts": "model",
    "ssm_inner": "model",
    "ssm_heads": "model",
    "seq_sp": "model",             # sequence parallelism for activations
    "kv_seq": "model",             # decode KV-cache sequence shard
    "layers": None,
    "head_dim": None,
    "ssm_state": None,
    "conv": None,
    "latent": None,
    "moe_mlp": None,               # expert-internal dim (EP already shards)
}


def _resolve_axis(rule_val, rules):
    """Follow one level of indirection (e.g. embed -> fsdp -> (pod, data))."""
    if isinstance(rule_val, str) and rule_val in rules:
        return rules[rule_val]
    return rule_val


def _mesh_axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def logical_to_pspec(axes: Sequence[Optional[str]], mesh: Mesh,
                     rules: Optional[Dict[str, Any]] = None,
                     shape: Optional[Sequence[int]] = None,
                     exclude: Optional[set] = None) -> P:
    """Translate logical axis names to a PartitionSpec under ``mesh``.

    Rules whose mesh axes are absent from the mesh, or whose dim size is not
    divisible by the mesh-axis size, fall back to replication. A mesh axis is
    never assigned twice in one spec (first dim wins). ``exclude`` drops
    specific mesh axes (e.g. Manual axes inside a shard_map region).
    """
    rules = rules or DEFAULT_RULES
    used: set = set(exclude or ())
    out = []
    for i, name in enumerate(axes):
        assignment = None
        if name is not None and name in rules:
            cand = _resolve_axis(rules[name], rules)
            if cand is not None:
                cand_t = cand if isinstance(cand, tuple) else (cand,)
                # keep only axes present in this mesh (e.g. "pod" is absent
                # on the single-pod mesh) and not already used in this spec
                cand_t = tuple(a for a in cand_t
                               if a in mesh.shape and a not in used)
                if cand_t:
                    size = _mesh_axis_size(mesh, cand_t)
                    if shape is None or shape[i] % size == 0:
                        assignment = cand_t if len(cand_t) > 1 else cand_t[0]
                        used.update(cand_t)
        out.append(assignment)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def make_shardings(specs, mesh: Mesh,
                   rules: Optional[Dict[str, Any]] = None) -> Any:
    """NamedSharding tree for a ParamSpec tree."""
    return jax.tree.map(
        lambda s: NamedSharding(
            mesh, logical_to_pspec(s.axes, mesh, rules, s.shape)),
        specs, is_leaf=_is_spec)


def constrain(x: jnp.ndarray, axes: Sequence[Optional[str]],
              mesh: Optional[Mesh] = None,
              rules: Optional[Dict[str, Any]] = None) -> jnp.ndarray:
    """Logical-axis sharding constraint; no-op outside a mesh context.

    Uses a bare PartitionSpec (resolved against the ambient mesh) so it
    composes with vmap and partial-manual shard_map regions. Inside a
    manual region (e.g. the pod-compressed step) the spec is resolved
    against the *context* AbstractMesh and Manual axes are excluded —
    only Auto axes may appear in a with_sharding_constraint there."""
    if manual_axis_sizes():
        # Inside a manual region: XLA's partitioner mishandles (and can
        # CHECK-crash on) sharding constraints under sdy.manual_computation;
        # rely on propagation from the operands' committed shardings.
        return x
    mesh = mesh or _current_mesh()
    if mesh is None or mesh.empty:
        return x
    spec = logical_to_pspec(axes, mesh, rules, x.shape)
    return jax.lax.with_sharding_constraint(x, spec)


def _current_mesh() -> Optional[Mesh]:
    """The mesh of an enclosing ``with mesh:`` block, or None.

    ``jax.sharding.get_mesh()`` reports only meshes entered through
    ``jax.set_mesh``; the training and dry-run paths enter theirs with
    ``with mesh:``, which JAX records only in its thread resources."""
    m = _jax_mesh.thread_resources.env.physical_mesh
    return None if m.empty else m


def manual_axis_sizes() -> dict:
    """{axis name: size} for mesh axes bound *manual* in the current trace
    (empty outside any ``jax.shard_map`` region)."""
    am = jax.sharding.get_abstract_mesh()
    return {a: am.shape[a] for a in am.manual_axes}


def match_vma(x: jnp.ndarray, *refs: Any) -> jnp.ndarray:
    """``x`` cast to vary over every manual mesh axis that some leaf of
    ``refs`` varies over (a no-op outside ``jax.shard_map``).

    A ``lax.scan`` carry must enter the loop with the varying axes the
    body gives it back.  Under the sharded serve dispatches the hidden
    state starts from replicated tokens but leaves each layer varying
    over ``slots``, because it has attended the slot-sharded cache."""
    want = frozenset().union(
        *(jax.typeof(r).vma for r in jax.tree.leaves(refs)))
    missing = tuple(sorted(want - jax.typeof(x).vma))
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def shardmap_mesh(mesh: Optional[Mesh]):
    """Mesh to pass to a nested ``jax.shard_map`` call.

    Inside an outer manual region (e.g. the pod-compressed tree-reduce
    shard_map, whose factored sub-axes rename "pod" -> "pod_t0"...), the
    context mesh is an AbstractMesh whose axis names differ from the
    original Mesh; shard_map then requires the *context* mesh. Outside any
    region, fall back to the caller-provided concrete mesh."""
    am = jax.sharding.get_abstract_mesh()
    if not am.empty:
        return am
    return mesh


def param_count(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=_is_spec)
    return int(sum(np.prod(s.shape) for s in leaves))


# ---------------------------------------------------------------------------
# Shared layer math
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5
             ) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * weight.astype(dt)


def rope_angles(positions: jnp.ndarray, dim: int, theta: float = 10000.0
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(sin, cos) tables for rotary embedding; positions (...,) int."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv   # (..., dim/2)
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jnp.ndarray, sin: jnp.ndarray, cos: jnp.ndarray
               ) -> jnp.ndarray:
    """Rotate pairs (even, odd) of the last axis. x: (..., S, H, D);
    sin/cos: (S, D/2) or broadcastable."""
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    # (S, D/2) -> (S, 1, D/2): align S against x's seq axis, broadcast batch
    # on the left and heads on the inserted axis.
    while sin.ndim < x1.ndim - 1:
        sin = sin[..., None, :]
        cos = cos[..., None, :]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray,
                       mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean token NLL; logits (..., V) fp32-promoted, labels (...) int."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
