"""Unified LM: dense / GQA / MLA / MoE decoder, encoder-only, VLM backbone.

One parameter declaration serves init, AOT dry-run specs, and sharding.
Layers are stacked on a leading ``layers`` axis and executed with
``lax.scan`` (+ optional remat), which keeps HLO size O(1) in depth — the
property that makes 26B-at-512-devices dry-runs compile in seconds.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention, mla, moe
from repro.models.common import (ParamSpec, constrain, cross_entropy_loss,
                                 match_vma, rms_norm, shardmap_mesh)
from repro.models.common import scan as mscan

__all__ = [
    "param_specs", "block_specs", "stack_specs",
    "forward", "train_loss", "decode_state_specs", "decode_step",
    "prefill_chunk", "verify_chunk", "verify_tree", "draft_head_specs",
    "hidden_states", "fit_draft_heads",
]


def stack_specs(per_layer: Any, n: int) -> Any:
    """Add a leading (n, ...) 'layers' axis to every spec in a tree."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes,
                            dtype=s.dtype, init=s.init, scale=s.scale),
        per_layer, is_leaf=lambda x: isinstance(x, ParamSpec))


def block_specs(cfg: ModelConfig) -> Dict[str, Any]:
    """One decoder/encoder block: pre-norm attention + pre-norm FFN."""
    d = cfg.d_model
    specs: Dict[str, Any] = {
        "attn_norm": ParamSpec((d,), ("embed",), init="ones"),
        "ffn_norm": ParamSpec((d,), ("embed",), init="ones"),
    }
    if cfg.attn_kind == "mla":
        specs["attn"] = mla.mla_param_specs(cfg)
    else:
        specs["attn"] = attention.gqa_param_specs(cfg)
    if cfg.n_experts:
        specs["ffn"] = moe.moe_param_specs(cfg)
    else:
        specs["ffn"] = moe.dense_ffn_specs(cfg)
    return specs


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    specs: Dict[str, Any] = {
        "embed": ParamSpec((cfg.vocab, d), ("vocab", "embed"), scale=0.02),
        "blocks": stack_specs(block_specs(cfg), cfg.n_layers),
        "final_norm": ParamSpec((d,), ("embed",), init="ones"),
        "lm_head": ParamSpec((d, cfg.vocab), ("embed", "vocab")),
    }
    if cfg.frontend:
        specs["frontend_proj"] = ParamSpec((cfg.frontend_dim, d),
                                           (None, "embed"))
    return specs


# ---------------------------------------------------------------------------
# embedding / logits (Megatron-style vocab parallelism via shard_map)
# ---------------------------------------------------------------------------

def _tp_size(mesh: Optional[Mesh]) -> int:
    if mesh is None or mesh.empty or "model" not in mesh.shape:
        return 1
    return mesh.shape["model"]


def vocab_parallel_embed(tokens: jnp.ndarray, table: jnp.ndarray,
                         mesh: Optional[Mesh], vocab: int,
                         enabled: bool = True) -> jnp.ndarray:
    """Masked local gather + psum over the model axis (VocabParallelEmbedding).
    Avoids the partitioner all-gathering the (V, D) table.

    Partial-manual shard_map: only the ``model`` axis is manual; batch/fsdp
    axes stay auto-partitioned, so no per-axis bookkeeping is needed here.
    """
    tp = _tp_size(mesh)
    if not enabled or tp == 1 or vocab % tp:
        return jnp.take(table, tokens, axis=0)
    v_local = vocab // tp

    def local(tok, tbl):
        shard = jax.lax.axis_index("model")
        lo = shard * v_local
        in_range = (tok >= lo) & (tok < lo + v_local)
        idx = jnp.clip(tok - lo, 0, v_local - 1)
        x = jnp.take(tbl, idx, axis=0)
        x = x * in_range[..., None].astype(x.dtype)
        return jax.lax.psum(x, "model")

    return jax.shard_map(local, mesh=shardmap_mesh(mesh),
                         axis_names=frozenset({"model"}),
                         in_specs=(P(), P("model", None)),
                         out_specs=P())(tokens, table)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _block_train(x: jnp.ndarray, bp: dict, cfg: ModelConfig,
                 mesh: Optional[Mesh]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    h = rms_norm(x, bp["attn_norm"], cfg.norm_eps)
    if cfg.attn_kind == "mla":
        h = mla.mla_train(h, bp["attn"], cfg)
    else:
        h = attention.gqa_train(h, bp["attn"], cfg)
    x = x + h
    x = constrain(x, ("batch", "seq_sp", None))
    h = rms_norm(x, bp["ffn_norm"], cfg.norm_eps)
    if cfg.n_experts:
        h, aux = moe.moe_ffn(h, bp["ffn"], cfg, mesh)
    else:
        h, aux = moe.dense_ffn(h, bp["ffn"], cfg), jnp.zeros((), jnp.float32)
    x = x + h
    x = constrain(x, ("batch", "seq_sp", None))
    return x, aux


def embed_inputs(params: dict, batch: Dict[str, jnp.ndarray],
                 cfg: ModelConfig, mesh: Optional[Mesh]) -> jnp.ndarray:
    """Token embedding + optional modality-frontend stub tokens (prepended)."""
    parts = []
    if cfg.frontend == "vision_stub" and "vision_embeds" in batch:
        ve = batch["vision_embeds"].astype(cfg.dtype)
        parts.append(ve @ params["frontend_proj"].astype(cfg.dtype))
    if cfg.frontend == "audio_stub":
        fr = batch["frames"].astype(cfg.dtype)
        x = fr @ params["frontend_proj"].astype(cfg.dtype)
        return constrain(x, ("batch", "seq_sp", None))
    tok = vocab_parallel_embed(batch["tokens"], params["embed"], mesh,
                               cfg.vocab, cfg.use_tp_shardmap
                               ).astype(cfg.dtype)
    parts.append(tok)
    x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    return constrain(x, ("batch", "seq_sp", None))


def forward(params: dict, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
            mesh: Optional[Mesh] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits (B, S, V), aux_loss)."""
    x = embed_inputs(params, batch, cfg, mesh)

    def layer(carry, bp):
        x, aux = carry
        x, aux_l = _block_train(x, bp, cfg, mesh)
        return (x, aux + aux_l), None

    if cfg.remat:
        layer = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), _ = mscan(layer, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["lm_head"].astype(x.dtype)
    logits = constrain(logits, ("batch", "seq_sp", "vocab"))
    return logits, aux


def train_loss(params: dict, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
               mesh: Optional[Mesh] = None) -> jnp.ndarray:
    logits, aux = forward(params, batch, cfg, mesh)
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:
        # VLM: loss on the text positions only (stub tokens are prepended)
        logits = logits[:, logits.shape[1] - labels.shape[1]:]
    return cross_entropy_loss(logits, labels, batch.get("loss_mask")) + aux


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def decode_state_specs(cfg: ModelConfig, batch: int, max_seq: int
                       ) -> Dict[str, ParamSpec]:
    """KV-cache layout (as ParamSpecs so dry-run/sharding derive from it).

    Every leaf's logical axes name both ``batch`` (the serve tier's slot
    axis — see ``repro.serve.cache``) and ``kv_seq`` (the position axis).
    A fully ``kv_seq``-positional tree is what makes prefix-cache page
    reuse sound; SSM/hybrid families return state leaves without it and
    are gated out of reuse by ``repro.serve.cache.supports_prefix``."""
    l, hd = cfg.n_layers, cfg.hd
    if cfg.attn_kind == "mla":
        return {
            "ckv": ParamSpec((l, batch, max_seq, cfg.kv_lora_rank),
                             ("layers", "batch", "kv_seq", None),
                             dtype=cfg.dtype, init="zeros"),
            "kr": ParamSpec((l, batch, max_seq, cfg.qk_rope_dim),
                            ("layers", "batch", "kv_seq", None),
                            dtype=cfg.dtype, init="zeros"),
        }
    return {
        "k": ParamSpec((l, batch, max_seq, cfg.n_kv_heads, hd),
                       ("layers", "batch", "kv_seq", None, None),
                       dtype=cfg.dtype, init="zeros"),
        "v": ParamSpec((l, batch, max_seq, cfg.n_kv_heads, hd),
                       ("layers", "batch", "kv_seq", None, None),
                       dtype=cfg.dtype, init="zeros"),
    }


def _decode_blocks(params: dict, state: Dict[str, jnp.ndarray],
                   batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
                   mesh: Optional[Mesh] = None
                   ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Run the block stack in cache-attend mode over C new tokens.

    batch: {"tokens": (B, C), "index": scalar current length OR a (B,)
    per-slot length vector (continuous batching), optional "pages": a
    (B, n_pages) int32 page table, optional "nspec": a (B,) per-slot
    valid-row count (speculative verification — cache writes for rows at
    or past it are masked off / redirected to the scratch page)}. When
    "pages" is present the state leaves are *physical page pools*
    (``(layers, num_pages, page_size, ...)``, see
    ``repro.serve.cache.paged_state_specs``) and every layer attends over
    gathered pages instead of dense slot rows. When the state additionally
    carries ``*_scale`` leaves (``repro.serve.cache.quant_state_specs``)
    the pools hold int8/packed-int4 codes; each layer receives a
    ``(codes, scales)`` pair and dequantizes in-kernel. Returns the final
    hidden states (B, C, D) and the updated cache state.

    Tree verification (:func:`verify_tree`) additionally passes
    ``"parents"`` (B, C) per-row parent indices, ``"pos_off"`` (B, C)
    per-row token-position offsets and ``"nchain"`` (B,) chain-row counts;
    every attention layer then ropes at ``index + pos_off``, masks with
    the ancestor mask, and commits only the chain rows through the page
    table (see :func:`repro.models.attention.gqa_decode_pages`)."""
    cur = batch["index"]
    pages = batch.get("pages")
    nspec = batch.get("nspec")
    tree = None
    if "parents" in batch:
        tree = (batch["parents"], batch["pos_off"], batch["nchain"])
    x = vocab_parallel_embed(batch["tokens"], params["embed"], mesh,
                             cfg.vocab, cfg.use_tp_shardmap).astype(cfg.dtype)
    x = match_vma(x, batch, state)

    if cfg.attn_kind == "mla":
        quant = "ckv_scale" in state
        if quant and pages is None:
            raise ValueError("quantized KV state requires a page table "
                             "(kv_dtype != 'fp32' is paged-only)")
        caches = (((state["ckv"], state["ckv_scale"]),
                   (state["kr"], state["kr_scale"])) if quant
                  else (state["ckv"], state["kr"]))

        def layer(x, inp):
            bp, ckv, kr = inp
            h = rms_norm(x, bp["attn_norm"], cfg.norm_eps)
            if pages is not None:
                h, ckv, kr = mla.mla_decode_paged(h, bp["attn"], cfg, ckv,
                                                  kr, cur, pages, nspec,
                                                  tree)
            else:
                h, ckv, kr = mla.mla_decode(h, bp["attn"], cfg, ckv, kr,
                                            cur, nspec, tree)
            x = x + h
            h = rms_norm(x, bp["ffn_norm"], cfg.norm_eps)
            if cfg.n_experts:
                h, _ = moe.moe_ffn(h, bp["ffn"], cfg, mesh)
            else:
                h = moe.dense_ffn(h, bp["ffn"], cfg)
            return x + h, (ckv, kr)

        x, (ckv, kr) = mscan(layer, x, (params["blocks"],) + caches)
        if quant:
            new_state = {"ckv": ckv[0], "ckv_scale": ckv[1],
                         "kr": kr[0], "kr_scale": kr[1]}
        else:
            new_state = {"ckv": ckv, "kr": kr}
    else:
        quant = "k_scale" in state
        if quant and pages is None:
            raise ValueError("quantized KV state requires a page table "
                             "(kv_dtype != 'fp32' is paged-only)")
        caches = (((state["k"], state["k_scale"]),
                   (state["v"], state["v_scale"])) if quant
                  else (state["k"], state["v"]))
        # splitk's shard_map assumes one shared write offset; paged split-K
        # is the single-host analogue keyed off the shared reduction plan.
        use_splitk = (not quant and pages is None and nspec is None and
                      jnp.ndim(cur) == 0 and
                      attention.splitk_ok(cfg, mesh, caches[0].shape[1],
                                          caches[0].shape[2]))
        page = cfg.decode_page_size
        use_paged = (not quant and pages is None and not use_splitk
                     and page > 0 and caches[0].shape[2] % page == 0)

        def layer(x, inp):
            bp, ck, cv = inp
            h = rms_norm(x, bp["attn_norm"], cfg.norm_eps)
            if pages is not None:
                h, ck, cv = attention.gqa_decode_pages(
                    h, bp["attn"], cfg, ck, cv, cur, pages, nspec, tree)
            elif use_splitk:
                h, ck, cv = attention.gqa_decode_splitk(
                    h, bp["attn"], cfg, ck, cv, cur, mesh)
            elif use_paged:
                h, ck, cv = attention.gqa_decode_paged(
                    h, bp["attn"], cfg, ck, cv, cur, page, nspec, tree)
            else:
                h, ck, cv = attention.gqa_decode(h, bp["attn"], cfg, ck, cv,
                                                 cur, nspec, tree)
            x = x + h
            h = rms_norm(x, bp["ffn_norm"], cfg.norm_eps)
            if cfg.n_experts:
                h, _ = moe.moe_ffn(h, bp["ffn"], cfg, mesh)
            else:
                h = moe.dense_ffn(h, bp["ffn"], cfg)
            return x + h, (ck, cv)

        x, (ck, cv) = mscan(layer, x, (params["blocks"],) + caches)
        if quant:
            new_state = {"k": ck[0], "k_scale": ck[1],
                         "v": cv[0], "v_scale": cv[1]}
        else:
            new_state = {"k": ck, "v": cv}
    return x, new_state


def decode_step(params: dict, state: Dict[str, jnp.ndarray],
                batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
                mesh: Optional[Mesh] = None
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One new token for every sequence. batch: {"tokens": (B, 1),
    "index": scalar current length or (B,) per-slot lengths, optional
    "pages": (B, n_pages) page table for pooled (paged-allocation) state}.
    Returns (logits (B, V), new state).

    Shape conventions the serve tier relies on: a ``(B,)`` index vector
    means every slot attends/writes at its own position (continuous
    batching); logits are always float32 regardless of ``cfg.dtype`` so
    in-graph sampling (``repro.serve.sampling.sample_tokens``) sees the
    same numerics as the greedy argmax path."""
    x, new_state = _decode_blocks(params, state, batch, cfg, mesh)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(x.dtype))[:, -1]
    return logits.astype(jnp.float32), new_state


def prefill_chunk(params: dict, state: Dict[str, jnp.ndarray],
                  batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
                  mesh: Optional[Mesh] = None
                  ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Ingest a whole prompt chunk in ONE dispatch (chunked prefill).

    batch: {"tokens": (B, C), "index": scalar chunk start offset,
    "nvalid": scalar count of real tokens in the chunk (<= C; trailing
    bucket padding beyond it only writes masked-off cache positions),
    optional "pages": (B, n_pages) page table for pooled state}.
    Returns (logits (B, V) at the last valid position, new state); logits
    are float32 (same guarantee as :func:`decode_step`, so the first
    sampled token of a request draws from the same numerics either way).
    """
    x, new_state = _decode_blocks(params, state, batch, cfg, mesh)
    nvalid = batch.get("nvalid")
    last = (jnp.asarray(x.shape[1] if nvalid is None else nvalid, jnp.int32)
            - 1)
    x_last = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
    x_last = rms_norm(x_last, params["final_norm"], cfg.norm_eps)
    logits = (x_last @ params["lm_head"].astype(x_last.dtype))[:, 0]
    return logits.astype(jnp.float32), new_state


def verify_chunk(params: dict, state: Dict[str, jnp.ndarray],
                 batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
                 mesh: Optional[Mesh] = None
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Score a (B, K+1) speculative token block in ONE dispatch.

    The serve tier's multi-token decode: each slot feeds its last sampled
    token plus up to K host-drafted candidates, and this call returns the
    next-token logits at **every** fed position — the wide parallel step
    that replaces K+1 sequential ``decode_step`` dispatches (the paper's
    sequential-to-combinatorial tilt applied to generation).

    batch: {"tokens": (B, K+1) fed tokens, "index": (B,) per-slot cache
    lengths, "nspec": (B,) per-slot count of *valid* fed rows (1 = no
    drafts; 0 = idle lane — every cache write masked off), optional
    "pages": (B, n_pages) page table for pooled state}.  KV rows for all
    valid fed positions are written through the cache/page table; rows at
    or past ``nspec`` (draft padding, idle lanes) are dropped or land on
    the scratch page, and the serve engine rewinds per-slot lengths (and
    releases any page advanced past the accepted point) after rejection.
    Returns (logits (B, K+1, V) float32, new state): ``logits[:, j]`` is
    the next-token distribution after fed token ``j``, same numerics
    guarantee as :func:`decode_step`.
    """
    x, new_state = _decode_blocks(params, state, batch, cfg, mesh)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["lm_head"].astype(x.dtype)
    return logits.astype(jnp.float32), new_state


def draft_head_specs(cfg: ModelConfig, n_heads: int,
                     head_dim: int = 64) -> Dict[str, ParamSpec]:
    """Medusa-style draft-head parameters: ``n_heads`` small residual MLPs
    over the final hidden state, sharing ``lm_head`` for their logits —
    head ``h`` predicts the token at offset ``h + 2`` from the position it
    reads (``+1`` is the ordinary next-token sample).  No draft model and
    no second KV cache: the heads run inside :func:`verify_tree` on hidden
    states the verify dispatch already computed.  The serve engine
    initializes these per model config when ``spec_drafter="heads"`` and
    carries them under ``params["draft_heads"]``."""
    d = cfg.d_model
    return {
        "w1": ParamSpec((n_heads, d, head_dim), (None, "embed", None)),
        "w2": ParamSpec((n_heads, head_dim, d), (None, None, "embed")),
    }


def _draft_head_top(params: dict, x: jnp.ndarray, head_topk: int
                    ) -> jnp.ndarray:
    """Top-``head_topk`` candidate tokens per draft head at every fed row:
    ``x`` is the final-normed hidden state (B, C, D); head ``h`` scores
    ``lm_head(x + silu(x @ w1[h]) @ w2[h])``.  Returns (B, C, H, A)
    int32, ranked by logit."""
    hp = params["draft_heads"]
    w1 = hp["w1"].astype(x.dtype)
    w2 = hp["w2"].astype(x.dtype)
    t = jax.nn.silu(jnp.einsum("bcd,hde->bhce", x, w1))
    xh = x[:, None] + jnp.einsum("bhce,hed->bhcd", t, w2)   # (B,H,C,D)
    head_logits = xh @ params["lm_head"].astype(x.dtype)    # (B,H,C,V)
    _, top = jax.lax.top_k(head_logits.astype(jnp.float32), head_topk)
    return jnp.swapaxes(top, 1, 2).astype(jnp.int32)        # (B,C,H,A)


def verify_tree(params: dict, state: Dict[str, jnp.ndarray],
                batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
                mesh: Optional[Mesh] = None, *, head_topk: int = 4
                ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray],
                           Dict[str, jnp.ndarray]]:
    """Score a (B, T+1) speculative token *tree* in ONE dispatch.

    The chain verifier (:func:`verify_chunk`) generalized: each slot feeds
    a block of ``nchain`` chain rows (the previous step's
    accepted-but-unmaterialized emitted tokens, committed to the cache /
    page pool at ``index + j``) followed by drafted tree rows whose
    topology is carried per-row — so a single compiled dispatch verifies a
    different tree shape per slot per step, the reconfigurable-width
    multi-operand step of the paper's Lemma 3 applied to generation.

    batch: {"tokens": (B, C) fed tokens, "index": (B,) per-slot committed
    cache lengths, "parents": (B, C) per-row parent row (``-1`` = attends
    committed cache only; chain row ``j`` has parent ``j - 1``; padding
    rows point at themselves), "pos_off": (B, C) per-row token-position
    offsets (chain row ``j`` is ``j``; a tree node is
    ``nchain - 1 + depth``), "nchain": (B,) chain rows per slot,
    "nspec": (B,) total valid rows per slot (0 = idle lane), optional
    "pages": (B, n_pages) page table}.  Every valid row's KV lands in the
    attended *view* at the row-unique position ``index + j``; only chain
    rows commit through the page table — drafted rows are redirected to
    the scratch page like over-draft rows, so rejected branches conserve
    page refcounts by construction.

    Returns ``(logits, head_top, new_state)``: logits (B, C, V) float32 at
    every fed row (same numerics guarantee as :func:`decode_step`);
    ``head_top`` is (B, C, H, ``head_topk``) int32 draft-head candidates
    when ``params["draft_heads"]`` is present (see
    :func:`draft_head_specs`), else ``None``.
    """
    x, new_state = _decode_blocks(params, state, batch, cfg, mesh)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["lm_head"].astype(x.dtype)
    head_top = (_draft_head_top(params, x, head_topk)
                if "draft_heads" in params else None)
    return logits.astype(jnp.float32), head_top, new_state


def hidden_states(params: dict, cfg: ModelConfig, tokens: jnp.ndarray
                  ) -> jnp.ndarray:
    """Teacher-forced final-normed hidden states for whole sequences.

    ``tokens`` is (B, L) int32; returns (B, L, D) float32 — exactly the
    ``x`` that :func:`verify_tree` hands the draft heads at each fed row
    (full causal attention over a fresh cache).  The training-side
    counterpart of the decode path: :func:`fit_draft_heads` regresses
    head targets against these.
    """
    b, l = tokens.shape
    specs = decode_state_specs(cfg, b, l)
    state = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs,
                         is_leaf=lambda s: isinstance(s, ParamSpec))
    batch = {"tokens": jnp.asarray(tokens, jnp.int32),
             "index": jnp.int32(0)}
    x, _ = _decode_blocks(params, state, batch, cfg, None)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x.astype(jnp.float32)


def fit_draft_heads(cfg: ModelConfig, params: dict,
                    streams: Any, *, n_heads: int = 4, head_dim: int = 64,
                    steps: int = 300, lr: float = 1e-2, seed: int = 0
                    ) -> Dict[str, jnp.ndarray]:
    """Train medusa-style draft heads (:func:`draft_head_specs`) by
    distillation on the model's own trajectories.

    Head ``h`` learns ``token[t + h + 2]`` from the teacher-forced hidden
    state at position ``t`` (offset ``+1`` is the ordinary ``lm_head``
    sample), with ``lm_head`` frozen and shared.  ``w2`` starts at zero,
    so each head begins as the plain next-token head and the residual MLP
    learns only the *offset* correction — the warm start that makes a few
    hundred full-batch Adam steps enough at toy scale.

    Args:
      streams: iterable of token id sequences (each longer than
        ``n_heads + 2``); e.g. completed request histories.
    Returns:
      {"w1", "w2"} float32 arrays to install under
      ``params["draft_heads"]``.
    """
    seqs = [list(s) for s in streams if len(s) > n_heads + 2]
    if not seqs:
        raise ValueError("fit_draft_heads needs a non-empty stream set")
    xs, ys, ms = [], [], []
    for s in seqs:
        t = jnp.asarray(s, jnp.int32)[None]
        x = hidden_states(params, cfg, t)[0]             # (L, D)
        l = len(s)
        tgt = jnp.zeros((n_heads, l), jnp.int32)
        mask = jnp.zeros((n_heads, l), jnp.float32)
        for h in range(n_heads):
            n_valid = max(l - h - 2, 0)
            tgt = tgt.at[h, :n_valid].set(t[0, h + 2:])
            mask = mask.at[h, :n_valid].set(1.0)
        xs.append(x); ys.append(tgt); ms.append(mask)
    x_all = jnp.concatenate(xs, axis=0)                  # (N, D)
    y_all = jnp.concatenate(ys, axis=1)                  # (H, N)
    m_all = jnp.concatenate(ms, axis=1)                  # (H, N)
    lm_head = params["lm_head"].astype(jnp.float32)

    key = jax.random.key(seed)
    d = cfg.d_model
    w1 = jax.random.normal(key, (n_heads, d, head_dim), jnp.float32) * 0.02
    w2 = jnp.zeros((n_heads, head_dim, d), jnp.float32)

    def loss_fn(w):
        t = jax.nn.silu(jnp.einsum("nd,hde->hne", x_all, w["w1"]))
        xh = x_all[None] + jnp.einsum("hne,hed->hnd", t, w["w2"])
        logits = xh @ lm_head                            # (H, N, V)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, y_all[..., None], axis=-1)[..., 0]
        return (nll * m_all).sum() / jnp.maximum(m_all.sum(), 1.0)

    @jax.jit
    def update(w, opt, i):
        g = jax.grad(loss_fn)(w)
        b1, b2, eps = 0.9, 0.999, 1e-8
        mu = jax.tree.map(lambda m, gg: b1 * m + (1 - b1) * gg, opt["mu"], g)
        nu = jax.tree.map(lambda v, gg: b2 * v + (1 - b2) * gg * gg,
                          opt["nu"], g)
        t = i + 1
        w = jax.tree.map(
            lambda p, m, v: p - lr * (m / (1 - b1 ** t))
            / (jnp.sqrt(v / (1 - b2 ** t)) + eps), w, mu, nu)
        return w, {"mu": mu, "nu": nu}

    w = {"w1": w1, "w2": w2}
    opt = {"mu": jax.tree.map(jnp.zeros_like, w),
           "nu": jax.tree.map(jnp.zeros_like, w)}
    for i in range(steps):
        w, opt = update(w, opt, jnp.float32(i))
    return {"w1": w["w1"], "w2": w["w2"]}
