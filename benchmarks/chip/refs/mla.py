"""Plain reference layer: pre-norm multi-head latent attention + SwiGLU.

One decoder layer in the published (expanded, not absorbed) form, in
float32 (see ``chipbench/reference.py`` for the shared pieces):

    h = rmsnorm(x) * g_attn
    q = (rmsnorm(h Wq_a) * g_q) Wq_b  -> per head [q_nope (dn) | q_rope (dr)]
    [c | k_r] = h Wkv_a;  c = rmsnorm(c) * g_kv        (latent, rank r_kv)
    k_nope = c Wk_b, v = c Wv_b                          (per head)
    q_rope, k_r = rope(q_rope, k_r)   (k_r is one head shared by all)
    a_h = softmax((q_nope.k_nope + q_rope.k_r) / sqrt(dn + dr), causal) v
    x = x + a Wo
    x = x + (silu(h' W1) * (h' W3)) W2,  h' = rmsnorm(x) * g_ffn

The cache of a layer is the latent ``c`` and the rotated ``k_r``.
Departures from a published model are stated in its configuration file.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from chipbench.reference import attend, rms_norm, rope, swiglu


def block_layout(m):
    d, h = m["d_model"], m["n_heads"]
    rq, rkv = m["q_lora_rank"], m["kv_lora_rank"]
    dn, dr, dv, f = (m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"],
                     m["d_ff"])
    return {"attn": {"wq_a": ((d, rq), "normal"), "q_norm": ((rq,), "ones"),
                     "wq_b": ((rq, h * (dn + dr)), "normal"),
                     "wkv_a": ((d, rkv + dr), "normal"),
                     "kv_norm": ((rkv,), "ones"),
                     "wk_b": ((rkv, h * dn), "normal"),
                     "wv_b": ((rkv, h * dv), "normal"),
                     "wo": ((h * dv, d), "normal")},
            "attn_norm": ((d,), "ones"),
            "ffn": {"w1": ((d, f), "normal"), "w3": ((d, f), "normal"),
                    "w2": ((f, d), "normal")},
            "ffn_norm": ((d,), "ones")}


def layer_weights(m):
    """Multiply-adds per token of one layer's weight matrices, in the
    expanded form above."""
    d, h = m["d_model"], m["n_heads"]
    rq, rkv = m["q_lora_rank"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    return (d * rq + rq * h * (dn + dr) + d * (rkv + dr)
            + rkv * h * (dn + dv) + h * dv * d + 3 * d * m["d_ff"])


def attn_per_key(m):
    """Multiply-adds of one query row against one key, over all heads
    (scores over nope and rope parts, and the weighted sum of values)."""
    return m["n_heads"] * (m["qk_nope_dim"] + m["qk_rope_dim"]
                           + m["v_head_dim"])


def empty_past(m):
    return (jnp.zeros((0, m["kv_lora_rank"]), jnp.float32),
            jnp.zeros((0, m["qk_rope_dim"]), jnp.float32))


def layer(x, w, pos, past, m, nx):
    r = x.shape[0]
    h, rkv = m["n_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    eps, theta = m["norm_eps"], m["rope_theta"]
    a = w["attn"]
    hx = rms_norm(x, w["attn_norm"], eps)
    q = nx.mm(rms_norm(nx.mm(hx, a["wq_a"]), a["q_norm"], eps), a["wq_b"])
    q = q.reshape(r, h, dn + dr)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, theta)
    kv = nx.mm(hx, a["wkv_a"])
    c = rms_norm(kv[:, :rkv], a["kv_norm"], eps)
    k_r = rope(kv[:, None, rkv:], pos, theta)[:, 0]
    c_all = jnp.concatenate([past[0], c], axis=0)
    kr_all = jnp.concatenate([past[1], k_r], axis=0)
    s = c_all.shape[0]
    k_nope = nx.mm(c_all, a["wk_b"]).reshape(s, h, dn)
    v = nx.mm(c_all, a["wv_b"]).reshape(s, h, dv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_all[:, None, :], (s, h, dr))], axis=-1)
    start = pos[0] - past[0].shape[0]
    k_pos = start + jnp.arange(s, dtype=jnp.int32)
    out = attend(jnp.concatenate([q_nope, q_rope], axis=-1), k, v, pos,
                 k_pos, 1.0 / math.sqrt(dn + dr), nx)
    x = x + nx.mm(out, a["wo"])
    x = x + swiglu(rms_norm(x, w["ffn_norm"], eps), w["ffn"], nx)
    return x, (c_all, kr_all)
