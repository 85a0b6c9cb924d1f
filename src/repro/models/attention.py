"""GQA attention: chunked-softmax training path + split-KV decode path.

Training uses query-chunked attention (flash-style outer loop at the JAX
level) so the (B, S, S) score tensor never materializes — the per-chunk
softmax-weighted combine *is* a multi-operand accumulation, and the decode
path's sharded-KV softmax is reduced across the model axis by the SPMD
partitioner (split-K decode: partial (max, sum, PV) accumulators combined —
the paper's reconfigured-adder pattern applied to attention).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import (ParamSpec, apply_rope, constrain,
                                 manual_axis_sizes, rope_angles,
                                 shardmap_mesh)
from repro.models.common import scan as mscan

__all__ = ["gqa_param_specs", "gqa_train", "gqa_decode", "gqa_decode_paged",
           "gqa_decode_pages", "decode_positions", "batched_cache_write",
           "masked_cache_write", "causal_valid", "ancestor_matrix",
           "tree_valid"]

NEG_INF = -1e30


def decode_positions(cur_index: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """Query positions for a decode/prefill call.

    ``cur_index`` is either a scalar (all sequences at the same length — the
    classic lockstep decode) or a per-sequence ``(B,)`` vector (continuous
    batching: every slot advances independently).  Returns ``(C,)`` positions
    for the scalar case and ``(B, C)`` for the vector case.
    """
    cur = jnp.asarray(cur_index, jnp.int32)
    offs = jnp.arange(chunk, dtype=jnp.int32)
    if cur.ndim == 0:
        return cur[None] + offs if chunk > 1 else cur[None]
    return cur[:, None] + offs[None, :]


def _rope_tables(positions: jnp.ndarray, dim: int, theta: float):
    """(sin, cos) shaped so they broadcast against (B, C, H, dim) queries:
    ``(C, 1, dim/2)`` for shared positions, ``(B, C, 1, dim/2)`` per-slot."""
    sin, cos = rope_angles(positions, dim, theta)
    return sin[..., None, :], cos[..., None, :]


def causal_valid(pos: jnp.ndarray, smax: int) -> jnp.ndarray:
    """Attendable-key mask for decode: key position s is visible to query
    c of sequence b iff s <= position(b, c).  ``pos`` is (C,) (shared
    positions) or (B, C) (per-slot); returns (1, 1, C, S) or (B, 1, C, S)
    ready to broadcast against (B, H, C, S) scores."""
    k_pos = jnp.arange(smax, dtype=jnp.int32)
    if pos.ndim == 1:
        return (k_pos[None, :] <= pos[:, None])[None, None]
    return (k_pos[None, None, :] <= pos[:, :, None])[:, None]


def ancestor_matrix(parents: jnp.ndarray) -> jnp.ndarray:
    """Ancestor-or-self reachability of a flattened token tree.

    ``parents`` is (B, C) int32: row ``j``'s parent row within the fed
    block (``-1`` = no in-block parent — the block root attends only the
    committed cache; padding rows point at themselves so they are never
    another row's ancestor).  Returns (B, C, C) bool with
    ``anc[b, q, r] == True`` iff row ``r`` is on row ``q``'s root path
    (including ``r == q``), built by walking the parent pointers ``C - 1``
    hops — ``C`` is the (small, static) verify-block width.
    """
    b, c = parents.shape
    rows = jnp.arange(c, dtype=jnp.int32)
    anc = jnp.broadcast_to(jnp.eye(c, dtype=bool)[None], (b, c, c))
    ptr = jnp.broadcast_to(rows[None], (b, c))
    for _ in range(c - 1):
        ptr = jnp.where(ptr >= 0,
                        jnp.take_along_axis(parents,
                                            jnp.clip(ptr, 0, c - 1), axis=1),
                        -1)
        anc = anc | (ptr[:, :, None] == rows[None, None, :])
    return anc


def tree_valid(index: jnp.ndarray, parents: jnp.ndarray,
               nvalid: jnp.ndarray, smax: int) -> jnp.ndarray:
    """Attendable-key mask for tree verification (the tree analogue of
    :func:`causal_valid`): key position ``s`` is visible to block row ``q``
    of slot ``b`` iff ``s < index[b]`` (committed cache — every committed
    position precedes the whole block), or ``s`` is the view position of a
    valid block row (``index[b] <= s < index[b] + nvalid[b]``) that is an
    ancestor-or-self of ``q`` per :func:`ancestor_matrix`.  Block rows are
    written at view positions ``index[b] + j`` (unique per row — sibling
    nodes never collide) while their rope/token positions come from the
    per-row depth, so the mask — not the write position — is what encodes
    the topology.  Returns (B, 1, C, smax), broadcastable against
    (B, H, C, S) scores.
    """
    index = jnp.asarray(index, jnp.int32)
    nvalid = jnp.asarray(nvalid, jnp.int32)
    b, c = parents.shape
    s = jnp.arange(smax, dtype=jnp.int32)
    committed = s[None, :] < index[:, None]                     # (B, S)
    kr = s[None, :] - index[:, None]                            # (B, S)
    in_block = (kr >= 0) & (kr < nvalid[:, None])               # (B, S)
    anc = ancestor_matrix(parents)                              # (B, C, C)
    krc = jnp.clip(kr, 0, c - 1)
    anc_qs = jnp.take_along_axis(
        anc, jnp.broadcast_to(krc[:, None, :], (b, c, smax)), axis=2)
    valid = committed[:, None, :] | (anc_qs & in_block[:, None, :])
    return valid[:, None]                                       # (B,1,C,S)


def batched_cache_write(cache: jnp.ndarray, new: jnp.ndarray,
                        cur_index: jnp.ndarray) -> jnp.ndarray:
    """Write ``new`` (B, C, ...) into ``cache`` (B, S, ...) at sequence
    offset ``cur_index`` — scalar (one shared offset) or (B,) (one offset
    per slot, vmapped dynamic_update_slice)."""
    new = new.astype(cache.dtype)
    zeros = (0,) * (cache.ndim - 2)
    if cur_index.ndim == 0:
        return jax.lax.dynamic_update_slice(cache, new,
                                            (0, cur_index) + zeros)
    return jax.vmap(
        lambda c, n, i: jax.lax.dynamic_update_slice(c, n, (i,) + zeros)
    )(cache, new, cur_index)


def masked_cache_write(cache: jnp.ndarray, new: jnp.ndarray,
                       pos: jnp.ndarray, nvalid: jnp.ndarray) -> jnp.ndarray:
    """Row-masked variant of :func:`batched_cache_write` for speculative
    verification: write row ``j`` of slot ``b`` at position ``pos[b, j]``
    only when ``j < nvalid[b]`` and the position is inside the cache.

    Invalid rows (draft lanes beyond a slot's proposed length, idle decode
    lanes with ``nvalid == 0``, or positions at/past capacity) are dropped
    outright — unlike ``dynamic_update_slice``, whose start clamping would
    silently overwrite *earlier* valid positions for near-capacity slots.
    """
    smax = cache.shape[1]
    new = new.astype(cache.dtype)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:
        pos = jnp.broadcast_to(pos[None], new.shape[:2])
    c = new.shape[1]
    valid = (jnp.arange(c, dtype=jnp.int32)[None] <
             jnp.asarray(nvalid, jnp.int32)[:, None]) & (pos < smax)
    tgt = jnp.where(valid, pos, smax)          # smax is out of range ...
    b_idx = jnp.arange(cache.shape[0], dtype=jnp.int32)[:, None]
    return cache.at[b_idx, tgt].set(new, mode="drop")   # ... -> dropped


def gqa_param_specs(cfg: ModelConfig, prefix_layers: bool = True) -> dict:
    """Per-layer attention params (leading layer axis added by the caller)."""
    hd, hq, hkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    d = cfg.d_model
    specs = {
        "wq": ParamSpec((d, hq * hd), ("embed", "q_heads")),
        "wk": ParamSpec((d, hkv * hd), ("embed", "kv_heads")),
        "wv": ParamSpec((d, hkv * hd), ("embed", "kv_heads")),
        "wo": ParamSpec((hq * hd, d), ("q_heads", "embed")),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((hq * hd,), ("q_heads",), init="zeros")
        specs["bk"] = ParamSpec((hkv * hd,), ("kv_heads",), init="zeros")
        specs["bv"] = ParamSpec((hkv * hd,), ("kv_heads",), init="zeros")
    return specs


def _project_qkv(x: jnp.ndarray, p: dict, cfg: ModelConfig
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    b, s, _ = x.shape
    hd, hq, hkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return (q.reshape(b, s, hq, hd), k.reshape(b, s, hkv, hd),
            v.reshape(b, s, hkv, hd))


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """Broadcast KV heads to the query-head count (keeps the sharded q-head
    axis layout instead of a split reshape the partitioner can't follow).
    Used by the auto-partitioned paths, :func:`gqa_train` and
    :func:`gqa_decode`; the paged split-K core (:func:`_splitk_attend`)
    and :func:`gqa_decode_splitk` group the queries per KV head instead."""
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def tp_head_pad(cfg: ModelConfig) -> int:
    """Heads to ADD so the q-head axis divides the model-axis size.

    40 heads on a 16-way model axis would otherwise fall back to fully
    REPLICATED attention — every shard computing all heads and psum-ing
    fp32 activations each layer (found by the §Perf roofline loop: the
    largest single contributor to llama4/qwen train-step wire bytes).
    Padding 40 -> 48 costs 20% extra attention FLOPs but shards them 16
    ways; padded q heads see zero queries and are sliced off before the
    output projection, so the math is exact."""
    from repro.models.common import _current_mesh
    mesh = _current_mesh()
    tp = 1
    if mesh is not None and "model" in mesh.shape:
        tp = mesh.shape["model"]
    else:
        am = jax.sharding.get_abstract_mesh()
        if not am.empty and "model" in am.shape:
            tp = dict(am.shape).get("model", 1)
        else:
            tp = manual_axis_sizes().get("model", 1)
    if tp <= 1 or cfg.n_heads % tp == 0:
        return 0
    # pad WITHIN each kv group (rep -> rep_pad) so the q-head -> kv-head
    # assignment of the real heads is preserved
    hkv = cfg.n_kv_heads
    rep = cfg.n_heads // hkv
    rep_pad = rep
    while (hkv * rep_pad) % tp and rep_pad < rep + tp:
        rep_pad += 1
    if (hkv * rep_pad) % tp:
        return 0
    return hkv * rep_pad - cfg.n_heads


def _pad_heads(x: jnp.ndarray, pad: int, hkv: int) -> jnp.ndarray:
    """Pad the q-head axis group-wise: (.., hkv*rep, hd) -> (.., hkv*rep_pad,
    hd) with zeros appended INSIDE each kv group."""
    if pad == 0:
        return x
    b, s, hq, hd = x.shape
    rep = hq // hkv
    rep_pad = (hq + pad) // hkv
    xg = x.reshape(b, s, hkv, rep, hd)
    xg = jnp.pad(xg, ((0, 0), (0, 0), (0, 0), (0, rep_pad - rep), (0, 0)))
    return xg.reshape(b, s, hkv * rep_pad, hd)


def _unpad_heads(x: jnp.ndarray, pad: int, hkv: int) -> jnp.ndarray:
    """Inverse of _pad_heads on the output: drop the in-group padded heads."""
    if pad == 0:
        return x
    b, s, hq_pad, hd = x.shape
    rep_pad = hq_pad // hkv
    rep = (hq_pad - pad) // hkv
    xg = x.reshape(b, s, hkv, rep_pad, hd)[:, :, :, :rep]
    return xg.reshape(b, s, hkv * rep, hd)


def _chunk_attend(q_chunk: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  q_offset: jnp.ndarray, causal: bool) -> jnp.ndarray:
    """Attend one query chunk against the full K/V. Shapes:
    q_chunk (B, C, H, hd); k/v (B, S, H, hd) -> (B, C, H, hd)."""
    hd = q_chunk.shape[-1]
    scores = jnp.einsum("bchd,bshd->bhcs", q_chunk, k) / jnp.sqrt(
        jnp.asarray(hd, jnp.float32)).astype(q_chunk.dtype)
    scores = scores.astype(jnp.float32)
    if causal:
        s = k.shape[1]
        c = q_chunk.shape[1]
        q_pos = q_offset + jnp.arange(c)[:, None]
        k_pos = jnp.arange(s)[None, :]
        scores = jnp.where((k_pos <= q_pos)[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_chunk.dtype)
    return jnp.einsum("bhcs,bshd->bchd", probs, v)


def gqa_train(x: jnp.ndarray, p: dict, cfg: ModelConfig,
              positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full-sequence attention, chunked over queries. x: (B, S, D)."""
    b, s, d = x.shape
    q, k, v = _project_qkv(x, p, cfg)
    if positions is None:
        positions = jnp.arange(s)
    sin, cos = rope_angles(positions, cfg.hd, cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)

    # TPU: streaming-softmax Pallas kernel — no S^2 HBM traffic. (Wrap the
    # whole step in shard_map on multi-chip meshes; the partitioner cannot
    # split a custom call.) CPU/dry-run lowers the chunked path below.
    from repro.kernels import ops as kops
    if (cfg.use_flash_attn and kops.on_tpu()
            and s % min(cfg.attn_chunk, 128) == 0):
        out = kops.flash_attention(q, k, v, causal=cfg.causal)
        out = out.reshape(b, s, cfg.n_heads * cfg.hd)
        out = constrain(out, ("batch", "seq_sp", None))
        return out @ p["wo"].astype(x.dtype)

    pad = tp_head_pad(cfg)
    hq = cfg.n_heads + pad
    q = _pad_heads(q, pad, cfg.n_kv_heads)
    n_rep = hq // cfg.n_kv_heads
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    q = constrain(q, ("batch", None, "q_heads", None))
    k = constrain(k, ("batch", None, "q_heads", None))
    v = constrain(v, ("batch", None, "q_heads", None))

    chunk = min(cfg.attn_chunk, s)
    if s % chunk:
        chunk = s  # fallback: unchunked for odd smoke shapes
    nc = s // chunk
    qc = jnp.moveaxis(q.reshape(b, nc, chunk, hq, cfg.hd), 1, 0)
    offsets = jnp.arange(nc) * chunk

    def body(_, qo):
        q_i, off = qo
        return None, _chunk_attend(q_i, k, v, off, cfg.causal)

    _, out = mscan(body, None, (qc, offsets))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, hq, cfg.hd)
    out = _unpad_heads(out, pad, cfg.n_kv_heads)
    out = out.reshape(b, s, cfg.n_heads * cfg.hd)
    out = constrain(out, ("batch", "seq_sp", None))
    return out @ p["wo"].astype(x.dtype)


def gqa_decode_splitk(x: jnp.ndarray, p: dict, cfg: ModelConfig,
                      cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                      cur_index: jnp.ndarray, mesh
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Split-K decode: FULL-manual shard_map; the KV cache never moves.

    The auto-partitioned path reshards the whole cache every step
    ("involuntary full rematerialization" in XLA's words) — ~30x the
    useful byte traffic on the 256-chip mesh. Here the cache is manual
    over (batch -> DP axes, kv_seq -> model): the owning shard writes the
    new KV in place, every shard attends q (replicated over model, tiny)
    against its local KV slice, and the partial (max, sum-exp, PV)
    accumulators are combined with psums — the paper's reconfigured
    multi-operand combine applied to attention (DESIGN.md §5)."""
    b, one, d = x.shape
    smax = cache_k.shape[1]
    tp = mesh.shape["model"]
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")
    s_loc = smax // tp
    q, k_new, v_new = _project_qkv(x, p, cfg)
    sin, cos = rope_angles(cur_index[None], cfg.hd, cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k_new = apply_rope(k_new, sin, cos)
    hkv, rep = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads

    def local(q, k_new, v_new, ck, cv, cur):
        i = jax.lax.axis_index("model")
        lo = i * s_loc
        pos = cur - lo
        write = (pos >= 0) & (pos < s_loc)
        pos_c = jnp.clip(pos, 0, s_loc - 1)
        # shard-local conditional write: only the owner updates its slice
        old_k = jax.lax.dynamic_slice(ck, (0, pos_c, 0, 0), k_new.shape)
        old_v = jax.lax.dynamic_slice(cv, (0, pos_c, 0, 0), v_new.shape)
        ck = jax.lax.dynamic_update_slice(
            ck, jnp.where(write, k_new.astype(ck.dtype), old_k),
            (0, pos_c, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cv, jnp.where(write, v_new.astype(cv.dtype), old_v),
            (0, pos_c, 0, 0))
        # grouped-head scores against the local KV slice (no repeat_kv)
        qg = q.reshape(b // max(1, _dp(mesh, dp_axes)), 1, hkv, rep, cfg.hd)
        scores = jnp.einsum("bqgrh,bsgh->bgrqs", qg, ck.astype(q.dtype))
        scores = scores.astype(jnp.float32) / math.sqrt(cfg.hd)
        valid = ((lo + jnp.arange(s_loc)) <= cur)[None, None, None, None]
        scores = jnp.where(valid, scores, NEG_INF)
        m_loc = jnp.max(scores, axis=-1)                      # (b,g,r,1)
        m = jax.lax.pmax(m_loc, "model")
        p_ = jnp.exp(scores - m[..., None])
        l_loc = jnp.sum(p_, axis=-1)
        o_loc = jnp.einsum("bgrqs,bsgh->bgrqh",
                           p_.astype(q.dtype), cv.astype(q.dtype))
        # the multi-operand combine: partial (l, o) accumulators psum'd
        l = jax.lax.psum(l_loc, "model")
        o = jax.lax.psum(o_loc.astype(jnp.float32), "model")
        out = (o / l[..., None]).astype(q.dtype)              # (b,g,r,1,h)
        out = jnp.moveaxis(out, 3, 1).reshape(-1, 1,
                                              cfg.n_heads * cfg.hd)
        return out, ck, cv

    batch_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes
                                                   else None)
    cache_spec = P(batch_spec, "model", None, None)
    out, cache_k, cache_v = jax.shard_map(
        local, mesh=shardmap_mesh(mesh),
        axis_names=frozenset(mesh.axis_names),
        in_specs=(P(batch_spec), P(batch_spec), P(batch_spec),
                  cache_spec, cache_spec, P()),
        out_specs=(P(batch_spec), cache_spec, cache_spec),
    )(q, k_new, v_new, cache_k, cache_v, cur_index)
    return out @ p["wo"].astype(x.dtype), cache_k, cache_v


def _dp(mesh, dp_axes) -> int:
    n = 1
    for a in dp_axes:
        n *= mesh.shape[a]
    return n


def splitk_ok(cfg: ModelConfig, mesh, batch: int, smax: int) -> bool:
    if mesh is None or getattr(mesh, "empty", True) or \
            "model" not in mesh.shape or mesh.shape["model"] <= 1:
        return False
    dp = _dp(mesh, tuple(a for a in mesh.axis_names if a != "model"))
    return smax % mesh.shape["model"] == 0 and batch % dp == 0


def _decode_qkv_new(x, p, cfg, cur, rope_pos=None):
    """Project + rope the C new tokens of a decode/prefill call.

    Returns ``(q, k_new, v_new, pos)`` where ``pos`` is the per-row cache
    *write* position (``(C,)`` for a scalar ``cur``, ``(B, C)`` for a
    per-slot vector).  q/k are roped at ``pos`` unless ``rope_pos`` is
    given (tree verification: sibling rows share a token position but
    write at distinct view positions — rope follows the token position,
    the write follows the row)."""
    c = x.shape[1]
    q, k_new, v_new = _project_qkv(x, p, cfg)
    pos = decode_positions(cur, c)                   # (C,) or (B, C)
    sin, cos = _rope_tables(pos if rope_pos is None else rope_pos,
                            cfg.hd, cfg.rope_theta)
    return apply_rope(q, sin, cos), apply_rope(k_new, sin, cos), v_new, pos


def _decode_qkv_cache(x, p, cfg, cache_k, cache_v, cur_index, nvalid=None,
                      tree=None):
    """Shared decode front-end: project + rope the C new tokens, write them
    into the cache at per-slot offsets, return (q, caches, valid mask).

    ``valid`` is (B or 1, 1, C, Smax): key position s is attendable by
    query c of sequence b iff s <= position(b, c).  With ``nvalid`` (a
    per-slot ``(B,)`` valid-row count — speculative verification), the
    cache writes are row-masked instead (:func:`masked_cache_write`).
    With ``tree`` (a ``(parents, pos_off, nchain)`` triple — tree
    verification, see :func:`gqa_decode_pages`), rope positions come from
    ``cur + pos_off`` and the mask is the ancestor mask
    (:func:`tree_valid`); the write positions stay row-unique."""
    smax = cache_k.shape[1]
    cur = jnp.asarray(cur_index, jnp.int32)
    rope_pos = None
    if tree is not None:
        parents, pos_off, _ = tree
        rope_pos = cur[:, None] + jnp.asarray(pos_off, jnp.int32)
    q, k_new, v_new, pos = _decode_qkv_new(x, p, cfg, cur, rope_pos)
    if nvalid is None:
        cache_k = batched_cache_write(cache_k, k_new, cur)
        cache_v = batched_cache_write(cache_v, v_new, cur)
    else:
        cache_k = masked_cache_write(cache_k, k_new, pos, nvalid)
        cache_v = masked_cache_write(cache_v, v_new, pos, nvalid)
    cache_k = constrain(cache_k, ("batch", "kv_seq", None, None))
    cache_v = constrain(cache_v, ("batch", "kv_seq", None, None))
    valid = (causal_valid(pos, smax) if tree is None
             else tree_valid(cur, tree[0], nvalid, smax))
    return q, cache_k, cache_v, valid


def gqa_decode(x: jnp.ndarray, p: dict, cfg: ModelConfig,
               cache_k: jnp.ndarray, cache_v: jnp.ndarray,
               cur_index: jnp.ndarray, nvalid=None, tree=None
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Cache-attend decode / chunked prefill. x: (B, C, D) — C == 1 is the
    classic one-token step, C > 1 ingests a whole prompt chunk in one call;
    ``cur_index`` is a scalar (lockstep) or (B,) vector (continuous
    batching, every slot at its own length). cache_{k,v}: (B, Smax, Hkv, hd)
    sharded (batch, kv_seq). ``nvalid``: optional (B,) per-slot valid-row
    count — rows past it are computed but never written (speculative
    verification). ``tree``: optional ``(parents, pos_off, nchain)``
    triple — tree verification with the ancestor mask (see
    :func:`gqa_decode_pages`). Returns (out, new_cache_k, new_cache_v).

    The softmax over the kv_seq-sharded axis lowers to partial max/sum
    accumulators all-reduced across the model axis — split-K decode as a
    multi-operand combine.
    """
    b, c, d = x.shape
    q, cache_k, cache_v, valid = _decode_qkv_cache(
        x, p, cfg, cache_k, cache_v, cur_index, nvalid, tree)

    pad = tp_head_pad(cfg)
    hq = cfg.n_heads + pad
    q = _pad_heads(q, pad, cfg.n_kv_heads)
    n_rep = hq // cfg.n_kv_heads
    k = _repeat_kv(cache_k.astype(x.dtype), n_rep)
    v = _repeat_kv(cache_v.astype(x.dtype), n_rep)
    scores = jnp.einsum("bchd,bshd->bhcs", q, k) / jnp.sqrt(
        jnp.asarray(cfg.hd, jnp.float32)).astype(x.dtype)
    scores = scores.astype(jnp.float32)
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhcs,bshd->bchd", probs, v)  # (b, C, hq, hd)
    out = _unpad_heads(out, pad, cfg.n_kv_heads)
    out = out.reshape(b, c, cfg.n_heads * cfg.hd)
    return out @ p["wo"].astype(x.dtype), cache_k, cache_v


def _splitk_attend(q: jnp.ndarray, k_view: jnp.ndarray, v_view: jnp.ndarray,
                   valid: jnp.ndarray, cfg: ModelConfig, page: int
                   ) -> jnp.ndarray:
    """Split-K attention over fixed-size KV pages (the shared core of
    :func:`gqa_decode_paged` and :func:`gqa_decode_pages`).

    q: (B, C, H, hd) roped queries; k_view/v_view: (B, Smax, Hkv, hd)
    contiguous *views* of the cache (dense slot rows or gathered pages —
    identical math either way); ``valid`` masks attendable positions.
    The queries are grouped per KV head, ``(B, C, Hkv, rep, hd)``, and
    each group is contracted against its one KV head in a single batched
    dot over ``(b, g)``: the view is never repeated to the query-head
    count.  Flattening ``(Hkv, rep)`` back gives the head order
    ``h = g * rep + r`` (``_pad_heads`` pads inside each group), so the
    partials are exactly those of a repeated-KV formulation.
    Each page contributes a partial (sum-exp, PV) accumulator under the
    global row max, and the page-axis combine is an explicit N-operand
    reduction routed through the same radix-4 tree plan
    (:func:`repro.dist.plan.make_reduction_plan`) that shapes the
    in-register, in-VMEM, and cross-device tiers — on TPU via the fused
    Pallas reduce, elsewhere via the identical in-register tree.
    Returns (B, C, n_heads * hd)."""
    import repro.dist.plan as dist_plan
    from repro.kernels import ops as kops
    from repro.kernels.moa_reduce import radix4_tree_sum

    b, c = q.shape[0], q.shape[1]
    smax = k_view.shape[1]
    n_pages = smax // page
    hkv = cfg.n_kv_heads
    pad = tp_head_pad(cfg)
    hq = cfg.n_heads + pad
    rep = hq // hkv
    q = _pad_heads(q, pad, hkv).reshape(b, c, hkv, rep, cfg.hd)
    k = k_view.astype(q.dtype)
    v = v_view.astype(q.dtype)
    scores = jnp.einsum("bcgrd,bsgd->bgrcs", q, k) / jnp.sqrt(
        jnp.asarray(cfg.hd, jnp.float32)).astype(q.dtype)
    scores = jnp.where(valid[:, :, None], scores.astype(jnp.float32),
                       NEG_INF)                              # (b,g,r,C,S)

    # split-K over pages: global row max, then per-page partial accumulators
    m = jnp.max(scores, axis=-1, keepdims=True)              # (b,g,r,C,1)
    p_ = jnp.exp(scores - m)                                 # (b,g,r,C,S)
    pp = p_.reshape(*p_.shape[:-1], n_pages, page)
    l_pages = jnp.moveaxis(pp.sum(axis=-1), -1, 0)         # (n_pages,b,g,r,C)
    vp = v.reshape(b, n_pages, page, hkv, cfg.hd)
    o_pages = jnp.einsum("bgrcns,bnsgd->nbgrcd",
                         pp.astype(q.dtype), vp)             # (n_pages,...)
    l_pages = l_pages.reshape(n_pages, b, hq, c)
    o_pages = o_pages.reshape(n_pages, b, hq, c, cfg.hd)

    plan = dist_plan.make_reduction_plan(n_pages)
    if kops.on_tpu():
        flat = lambda t: kops.moa_reduce(
            t.reshape(n_pages, t.shape[1], -1)).reshape(t.shape[1:])
        l, o = flat(l_pages), flat(o_pages.astype(jnp.float32))
    else:
        l = radix4_tree_sum(l_pages, plan)
        o = radix4_tree_sum(o_pages.astype(jnp.float32), plan)
    out = (o / l[..., None]).astype(q.dtype)                 # (b,h,C,hd)
    out = jnp.moveaxis(out, 1, 2)                            # (b,C,h,hd)
    out = _unpad_heads(out, pad, hkv)
    return out.reshape(b, c, cfg.n_heads * cfg.hd)


def gqa_decode_paged(x: jnp.ndarray, p: dict, cfg: ModelConfig,
                     cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                     cur_index: jnp.ndarray, page: int, nvalid=None,
                     tree=None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Paged split-K decode over a *dense* per-slot cache: the serve
    engine's hot path as the fourth consumer of the shared reduction
    engine.

    The KV cache is viewed as ``n_pages`` fixed-size pages along the
    sequence axis; the page-axis combine runs through the shared radix-4
    :class:`~repro.dist.plan.ReductionPlan` (see :func:`_splitk_attend`).
    Identical math to :func:`gqa_decode` up to fp reassociation of the
    page sums.
    """
    smax = cache_k.shape[1]
    if smax % page:
        raise ValueError(f"page={page} must divide max_seq={smax}")
    q, cache_k, cache_v, valid = _decode_qkv_cache(
        x, p, cfg, cache_k, cache_v, cur_index, nvalid, tree)
    out = _splitk_attend(q, cache_k, cache_v, valid, cfg, page)
    return out @ p["wo"].astype(x.dtype), cache_k, cache_v


def gqa_decode_pages(x: jnp.ndarray, p: dict, cfg: ModelConfig,
                     pool_k: jnp.ndarray, pool_v: jnp.ndarray,
                     cur_index: jnp.ndarray, pages: jnp.ndarray, nvalid=None,
                     tree=None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Paged-*allocation* split-K decode: :func:`gqa_decode_paged`
    generalized to take a page-index vector per slot.

    pool_k/pool_v: ``(num_pages, page_size, Hkv, hd)`` physical page pools
    (this layer's slice of the serve tier's pooled state tree); ``pages``:
    ``(B, n_pages)`` int32 page table mapping each slot's logical pages to
    physical ones.  The slot views are *gathered* from the pool
    (:func:`repro.models.paging.gather_pages`) — non-contiguous, possibly
    refcount-shared pages — then attended with exactly the same split-K
    page combine as the dense path, so tokens are bit-exact with a
    contiguous engine.  The ``C`` new KV rows are scattered back through
    the table; shared pages are never rewritten (the serve engine
    copy-on-writes the boundary page before any write can land there).
    ``nvalid``: optional (B,) per-slot valid-row count — rows past it are
    redirected to the scratch page (speculative verification's write mask).

    **Tree verification** (``tree = (parents, pos_off, nchain)``): the fed
    block is a chain part (``nchain[b]`` rows — the previous step's
    accepted-but-unmaterialized tokens, committed through the page table
    at positions ``index + j``) followed by drafted tree rows.  Every
    valid row writes its KV into the gathered *view* at the row-unique
    position ``index + j`` (so sibling keys never collide and descendants
    can attend their ancestors), rope/token positions come from
    ``index + pos_off`` (per-row depth), attention uses the ancestor mask
    (:func:`tree_valid` over ``parents``), and the pool scatter uses
    ``nchain`` as its row count — drafted rows land on the scratch page
    exactly like over-draft rows, so rejected branches never touch
    refcounted pages and need no pool rollback.

    **Quantized pages**: each pool argument may instead be a
    ``(codes, scales)`` pair (int8 / packed-int4 code pool + fp32 per-row
    scale pool, see :func:`repro.serve.cache.quant_state_specs`).  The
    gathered view is dequantized in-kernel
    (:func:`repro.models.paging.gather_pages_dequant`), the new rows are
    written into the view at full precision (scores/softmax stay fp32
    either way), and quantization happens on scatter — codes and their
    scales through the same page table.  Returns the updated pools in the
    same structure they came in.
    """
    from repro.models import paging, quant_kv

    b, c, _ = x.shape
    quant = isinstance(pool_k, tuple)
    if quant:
        (codes_k, scale_k), (codes_v, scale_v) = pool_k, pool_v
        page = codes_k.shape[1]
        bits = quant_kv.kv_bits(codes_k)
        k_gath = paging.gather_pages_dequant(codes_k, scale_k, pages,
                                             x.dtype)
        v_gath = paging.gather_pages_dequant(codes_v, scale_v, pages,
                                             x.dtype)
    else:
        page = pool_k.shape[1]
        k_gath = paging.gather_pages(pool_k, pages)
        v_gath = paging.gather_pages(pool_v, pages)
    smax = pages.shape[1] * page
    cur = jnp.asarray(cur_index, jnp.int32)
    rope_pos = None
    scatter_n = nvalid
    if tree is not None:
        parents, pos_off, nchain = tree
        rope_pos = cur[:, None] + jnp.asarray(pos_off, jnp.int32)
        scatter_n = nchain
    q, k_new, v_new, pos = _decode_qkv_new(x, p, cfg, cur, rope_pos)
    if nvalid is None:
        k_view = batched_cache_write(k_gath, k_new, cur)
        v_view = batched_cache_write(v_gath, v_new, cur)
    else:
        # row-masked view write: near capacity, a (B, K+1) block can hang
        # past smax, and dynamic_update_slice's start clamping would shift
        # the fed rows over *valid* view positions — drop them instead
        # (their queries are draft padding whose outputs are discarded)
        k_view = masked_cache_write(k_gath, k_new, pos, nvalid)
        v_view = masked_cache_write(v_gath, v_new, pos, nvalid)
    valid = (causal_valid(pos, smax) if tree is None
             else tree_valid(cur, tree[0], nvalid, smax))
    out = _splitk_attend(q, k_view, v_view, valid, cfg, page)
    if quant:
        qk, sk = quant_kv.quantize_rows(k_new, bits)
        qv, sv = quant_kv.quantize_rows(v_new, bits)
        codes_k = paging.scatter_token_rows(codes_k, pages, qk, pos,
                                            scatter_n)
        scale_k = paging.scatter_token_rows(scale_k, pages, sk, pos,
                                            scatter_n)
        codes_v = paging.scatter_token_rows(codes_v, pages, qv, pos,
                                            scatter_n)
        scale_v = paging.scatter_token_rows(scale_v, pages, sv, pos,
                                            scatter_n)
        return (out @ p["wo"].astype(x.dtype), (codes_k, scale_k),
                (codes_v, scale_v))
    pool_k = paging.scatter_token_rows(pool_k, pages, k_new, pos, scatter_n)
    pool_v = paging.scatter_token_rows(pool_v, pages, v_new, pos, scatter_n)
    return out @ p["wo"].astype(x.dtype), pool_k, pool_v
