"""The plain reference: a decoder forward pass in float32, and its control.

It imports nothing of the program.  The weights are drawn from ``--seed``
the way the serve path draws its own (``repro.launch.serve.
init_serve_params``): one key per leaf, split from ``jax.random.key(seed)``
in sorted-path order; normal leaves are ``normal * scale`` with
``scale = 1/sqrt(fan_in)`` unless stated; norms are ones and biases zeros;
every leaf is stored in the served type (bf16).  The layer equations live
in ``refs/<kind>.py`` beside the configurations; this module holds the
embedding, the final norm and head, rotary embedding, causal attention,
and the arithmetic:

* the reference computes every product in float32 at ``HIGHEST``
  precision from the bf16 weights;
* the control computes every product with both operands rounded to fp8
  (e4m3, one scale per tensor): the next precision below the bf16 that
  the configurations state.

A served token's *gap* is how far its reference logit lies below the
reference's best logit at that position.  For the control, the token is
the one its own logits put first at the same position of the same prompt
and served tokens.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: largest finite float8_e4m3fn
_E4M3_MAX = 448.0
#: query rows attended per block (bounds the score tensor's size)
_Q_BLOCK = 512


# --------------------------------------------------------------- numerics
def _fp8(x):
    """Round ``x`` to e4m3 with one scale for the whole tensor."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / _E4M3_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


class Numerics:
    """The products of a forward pass: exact float32, or the fp8 control."""

    def __init__(self, control: bool):
        self.control = control

    def _ops(self, ops):
        return [_fp8(o) for o in ops] if self.control else list(ops)

    def mm(self, a, b):
        a, b = self._ops((a, b))
        return jnp.matmul(a, b, precision=HIGHEST)

    def einsum(self, spec, a, b):
        a, b = self._ops((a, b))
        return jnp.einsum(spec, a, b, precision=HIGHEST)


# ----------------------------------------------------------------- pieces
def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope(x, pos, theta):
    """Rotary embedding of ``x`` (R, H, D) at positions ``pos`` (R,):
    dimension pairs (2i, 2i+1) rotate by ``pos / theta**(2i/D)``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv                  # (R, D/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attend(q, k, v, q_pos, k_pos, scale, nx: Numerics):
    """Causal softmax attention, one block of query rows at a time.

    q: (R, H, dq); k: (S, H, dq); v: (S, H, dv); key s is visible to query
    r iff ``k_pos[s] <= q_pos[r]``.  Returns (R, H * dv)."""
    r, h = q.shape[0], q.shape[1]
    blk = min(_Q_BLOCK, r)
    assert r % blk == 0, (r, blk)

    def block(args):
        qb, pb = args
        s = nx.einsum("rhd,shd->hrs", qb, k) * scale
        s = jnp.where(k_pos[None, None, :] <= pb[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return nx.einsum("hrs,shd->rhd", p, v)

    out = jax.lax.map(block, (q.reshape(r // blk, blk, h, q.shape[-1]),
                              q_pos.reshape(r // blk, blk)))
    return out.reshape(r, -1)


def swiglu(x, w, nx: Numerics):
    return nx.mm(jax.nn.silu(nx.mm(x, w["w1"])) * nx.mm(x, w["w3"]),
                 w["w2"])


# ---------------------------------------------------------------- weights
def model_layout(model: Dict[str, Any], block: Dict[str, Any]
                 ) -> Dict[str, Any]:
    """The whole parameter tree: ``block`` (one layer's leaves, each
    ``(shape, init[, scale])``) stacked ``n_layers`` deep, plus the
    embedding, final norm and head."""
    n, d, v = model["n_layers"], model["d_model"], model["vocab"]

    def stack(t):
        if isinstance(t, dict):
            return {k: stack(x) for k, x in t.items()}
        return ((n,) + tuple(t[0]),) + tuple(t[1:])

    return {"blocks": stack(block),
            "embed": ((v, d), "normal", 0.02),
            "final_norm": ((d,), "ones"),
            "lm_head": ((d, v), "normal")}


def _flatten(tree, path=()):
    """Leaves in sorted-key order (the order a pytree of dicts flattens)."""
    if isinstance(tree, dict):
        out = []
        for k in sorted(tree):
            out += _flatten(tree[k], path + (k,))
        return out
    return [(path, tree)]


def _leaf(spec, key, dtype):
    shape, init = tuple(spec[0]), spec[1]
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "ones":
        return jnp.ones(shape, dtype)
    scale = spec[2] if len(spec) > 2 else None
    if scale is None:
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        scale = 1.0 / math.sqrt(max(1, fan_in))
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def init_weights(layout: Dict[str, Any], seed: int, dtype) -> Dict:
    """Every leaf of ``layout`` from ``seed``, in one program on the
    device, stored in ``dtype``."""
    leaves = _flatten(layout)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        return [_leaf(spec, k, dtype) for (_, spec), k in zip(leaves, keys)]

    vals = jax.jit(make)(jax.random.key(seed))
    out: Dict = {}
    for (path, _), val in zip(leaves, vals):
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = val
    return out


# ---------------------------------------------------------------- forward
class Reference:
    """The plain forward of one configuration over its seeded weights.

    ``kind`` is the layer module (``refs/<kind>.py``): it gives
    ``block_layout(model)``, ``empty_past(model)`` and
    ``layer(x, w, pos, past, model, nx) -> (x, cache)``, where ``cache``
    is this layer's keys and values (in its own layout) for ``past`` and
    the new rows together."""

    def __init__(self, model: Dict[str, Any], kind, seed: int, dtype):
        self.model = model
        self.kind = kind
        self.weights = init_weights(
            model_layout(model, kind.block_layout(model)), seed, dtype)
        self._layer = jax.jit(self._layer_impl, static_argnums=(5,))
        self._head = jax.jit(self._head_impl, static_argnums=(3,))

    def _layer_impl(self, x, blocks, l, pos, past, control):
        w = jax.tree.map(lambda a: a[l].astype(F32), blocks)
        return self.kind.layer(x, w, pos, past, self.model,
                               Numerics(control))

    def _head_impl(self, x, final_norm, lm_head, control):
        h = rms_norm(x, final_norm.astype(F32), self.model["norm_eps"])
        return Numerics(control).mm(h, lm_head.astype(F32))

    def hidden(self, tokens: np.ndarray, start: int, pasts=None,
               control: bool = False) -> Tuple[jnp.ndarray, List]:
        """Final hidden rows of ``tokens`` at positions ``start..``, after
        ``pasts`` (one per layer; None = nothing before ``start``), and
        every layer's cache for everything up to the last row."""
        w = self.weights
        x = jnp.take(w["embed"], jnp.asarray(tokens, jnp.int32),
                     axis=0).astype(F32)
        pos = jnp.arange(start, start + len(tokens), dtype=jnp.int32)
        caches = []
        for l in range(self.model["n_layers"]):
            past = (pasts[l] if pasts is not None
                    else self.kind.empty_past(self.model))
            x, cache = self._layer(x, w["blocks"], l, pos, past, control)
            caches.append(cache)
        return x, caches

    def logits(self, rows: jnp.ndarray, control: bool = False):
        w = self.weights
        return self._head(rows, w["final_norm"], w["lm_head"], control)


def served_gaps(ref: Reference, prompt: Sequence[int],
                served: Sequence[int], *, prefix_len: int, pad_len: int,
                pad_rows: int, pasts=None, control_pasts=None,
                control: bool = False) -> Dict[str, np.ndarray]:
    """Gaps of one request's served tokens under the reference.

    The sequence ``prompt + served`` is run from position ``prefix_len``
    (with ``pasts`` holding the shared prefix's caches, and
    ``control_pasts`` the control's), padded to
    ``pad_len`` rows so one compiled program serves every request; padding
    follows the real rows, so causal attention keeps it out of them.
    Returns ``{"gap": ...}`` per served token, and with ``control`` also
    ``{"control_gap": ...}``, the gap of the token the control puts first.
    """
    seq = list(prompt) + list(served)
    body = seq[prefix_len:]
    if len(body) > pad_len or len(served) > pad_rows:
        raise ValueError(f"request of {len(body)} rows / {len(served)} "
                         f"served tokens exceeds {pad_len} / {pad_rows}")
    toks = np.zeros(pad_len, np.int32)
    toks[:len(body)] = body
    first = len(prompt) - 1 - prefix_len      # row that predicts served[0]
    idx = np.full(pad_rows, first, np.int32)
    idx[:len(served)] = first + np.arange(len(served))
    tgt = np.zeros(pad_rows, np.int32)
    tgt[:len(served)] = served
    out = {}
    x, _ = ref.hidden(toks, prefix_len, pasts)
    lg = ref.logits(x[idx])
    best = jnp.max(lg, axis=-1)
    gap = best - jnp.take_along_axis(lg, tgt[:, None], axis=-1)[:, 0]
    out["gap"] = np.asarray(gap)[:len(served)]
    if control:
        xc, _ = ref.hidden(toks, prefix_len, control_pasts, control=True)
        ctok = jnp.argmax(ref.logits(xc[idx], control=True), axis=-1)
        cgap = best - jnp.take_along_axis(lg, ctok[:, None], axis=-1)[:, 0]
        out["control_gap"] = np.asarray(cgap)[:len(served)]
    return out


def prefix_pasts(ref: Reference, prefix: Sequence[int], control: bool):
    """Every layer's cache after the shared prefix (computed once)."""
    if not prefix:
        return None
    _, caches = ref.hidden(np.asarray(prefix, np.int32), 0, None, control)
    return caches
