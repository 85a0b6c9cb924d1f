"""Plain reference layer: pre-norm grouped-query attention + SwiGLU.

One decoder layer, written from its equations in float32 (see
``chipbench/reference.py`` for the shared pieces and the arithmetic):

    h = rmsnorm(x) * g_attn
    q = h Wq + bq, k = h Wk + bk, v = h Wv + bv      (bias if qkv_bias)
    q, k = rope(q, k) over the whole head (theta = rope_theta)
    a_h = softmax(q_h k_{h//g}^T / sqrt(head_dim), causal) v_{h//g}
          with g = n_heads / n_kv_heads query heads per key-value head
    x = x + a Wo
    x = x + (silu(h' W1) * (h' W3)) W2,  h' = rmsnorm(x) * g_ffn

Departures from a published model are stated in its configuration file;
this is the arithmetic the serve path is meant to compute.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from chipbench.reference import attend, rms_norm, rope, swiglu


def block_layout(m):
    d, hd = m["d_model"], m["head_dim"]
    hq, hkv, f = m["n_heads"] * hd, m["n_kv_heads"] * hd, m["d_ff"]
    attn = {"wq": ((d, hq), "normal"), "wk": ((d, hkv), "normal"),
            "wv": ((d, hkv), "normal"), "wo": ((hq, d), "normal")}
    if m.get("qkv_bias"):
        attn.update(bq=((hq,), "zeros"), bk=((hkv,), "zeros"),
                    bv=((hkv,), "zeros"))
    return {"attn": attn, "attn_norm": ((d,), "ones"),
            "ffn": {"w1": ((d, f), "normal"), "w3": ((d, f), "normal"),
                    "w2": ((f, d), "normal")},
            "ffn_norm": ((d,), "ones")}


def layer_weights(m):
    """Multiply-adds per token of one layer's weight matrices."""
    d, hd = m["d_model"], m["head_dim"]
    hq, hkv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    return d * (hq + 2 * hkv) + hq * d + 3 * d * m["d_ff"]


def attn_per_key(m):
    """Multiply-adds of one query row against one key, over all heads
    (scores and the weighted sum of values)."""
    return 2 * m["n_heads"] * m["head_dim"]


def empty_past(m):
    z = jnp.zeros((0, m["n_kv_heads"], m["head_dim"]), jnp.float32)
    return (z, z)


def layer(x, w, pos, past, m, nx):
    r = x.shape[0]
    h_q, h_kv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a = w["attn"]
    h = rms_norm(x, w["attn_norm"], m["norm_eps"])
    q, k, v = nx.mm(h, a["wq"]), nx.mm(h, a["wk"]), nx.mm(h, a["wv"])
    if m.get("qkv_bias"):
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q.reshape(r, h_q, hd), pos, m["rope_theta"])
    k = rope(k.reshape(r, h_kv, hd), pos, m["rope_theta"])
    v = v.reshape(r, h_kv, hd)
    k_all = jnp.concatenate([past[0], k], axis=0)
    v_all = jnp.concatenate([past[1], v], axis=0)
    start = pos[0] - past[0].shape[0]
    k_pos = start + jnp.arange(k_all.shape[0], dtype=jnp.int32)
    g = h_q // h_kv
    out = attend(q, jnp.repeat(k_all, g, axis=1), jnp.repeat(v_all, g, axis=1),
                 pos, k_pos, 1.0 / math.sqrt(hd), nx)
    x = x + nx.mm(out, a["wo"])
    x = x + swiglu(rms_norm(x, w["ffn_norm"], m["norm_eps"]), w["ffn"], nx)
    return x, (k_all, v_all)
