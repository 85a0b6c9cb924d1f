"""In-graph token sampling for the serve engine.

One jitted dispatch samples every live slot at once: the engine passes the
per-slot sampling knobs as ``(B,)`` lanes (temperature / top-k / top-p /
seed / sample index) alongside the ``(B, V)`` logits, and
:func:`sample_tokens` returns one token id per slot without leaving the
graph.  Randomness is *stateless*: each draw keys off
``fold_in(PRNGKey(seed), sample_index)``, so a request's token stream is a
pure function of ``(seed, sample_index)`` — identical across engine
restarts, slot assignments, eviction/re-admission and batch composition
(given identical logits).

``temperature == 0`` is the greedy fast path: the returned token is exactly
``argmax(logits)``, bit-for-bit the greedy engine's behaviour, so greedy
serving is unaffected by the sampling plumbing.  A dispatch whose every
lane is greedy runs the argmax alone: :func:`sample_tokens` branches once,
on the device, on whether any lane samples, so the vocabulary-wide sort,
softmax and cumsum run only in dispatches that need them.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["SamplingParams", "GREEDY", "sample_tokens", "sampling_lanes"]


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration (host-side, hashable).

    Args:
      temperature: softmax temperature; ``0`` selects greedy decoding
        (exact ``argmax``, the default and the bit-exact fast path).
      top_k: keep only the ``top_k`` highest-logit tokens before sampling;
        ``0`` (or ``>= vocab``) disables the truncation.
      top_p: nucleus truncation — keep the smallest set of tokens whose
        cumulative probability reaches ``top_p``; ``1.0`` disables it.
      seed: per-request PRNG seed. Together with the running sample index
        it fully determines the request's random draws.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 <= self.top_p <= 1.0:
            raise ValueError(f"top_p must be in [0, 1], got {self.top_p}")

    @property
    def is_greedy(self) -> bool:
        """True when this request always takes the argmax fast path."""
        return self.temperature == 0.0


#: The default request policy: argmax decoding, no randomness.
GREEDY = SamplingParams()


def sampling_lanes(params_per_slot, sample_idx_per_slot
                   ) -> Tuple[jnp.ndarray, ...]:
    """Pack per-slot :class:`SamplingParams` into the ``(B,)`` lane arrays.

    Args:
      params_per_slot: sequence of B :class:`SamplingParams` (one per slot;
        empty slots should carry :data:`GREEDY`).
      sample_idx_per_slot: sequence of B ints — how many tokens each slot's
        request has sampled so far (the stateless PRNG stream position).

    Returns:
      ``(temps, top_ks, top_ps, seeds, idxs)`` arrays of shape ``(B,)``,
      ready to pass to :func:`sample_tokens`.
    """
    sp = list(params_per_slot)
    return (jnp.asarray([p.temperature for p in sp], jnp.float32),
            jnp.asarray([p.top_k for p in sp], jnp.int32),
            jnp.asarray([p.top_p for p in sp], jnp.float32),
            jnp.asarray([p.seed for p in sp], jnp.int32),
            jnp.asarray(list(sample_idx_per_slot), jnp.int32))


def _sample_row(logits: jnp.ndarray, temp: jnp.ndarray, top_k: jnp.ndarray,
                top_p: jnp.ndarray, seed: jnp.ndarray, idx: jnp.ndarray
                ) -> jnp.ndarray:
    """Sample one token id from one slot's ``(V,)`` logits (traced body).

    The temp/top_k/top_p/seed/idx scalars are this slot's lane values; see
    :func:`sample_tokens` for their semantics. Works in sorted space so the
    top-k / top-p truncations are rank masks and no scatter is needed.
    """
    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits).astype(jnp.int32)

    # descending sort once; temperature rescales monotonically, so the
    # logit order and the scaled-prob order coincide.  The sort carries the
    # values with the indices: negating its keys back gives logits[order]
    # exactly, without a vocabulary-wide gather
    ranks = jnp.arange(vocab)
    neg_sorted, order = jax.lax.sort((-logits, ranks), num_keys=1)
    scaled = -neg_sorted / jnp.maximum(temp, 1e-6)

    kk = jnp.where(top_k <= 0, vocab, top_k)
    keep = ranks < kk
    probs = jax.nn.softmax(scaled)
    # nucleus: keep tokens whose cumulative mass *before* them is < top_p
    # (the token that crosses the threshold is included); rank 0 always
    # survives so the distribution is never empty
    keep &= (jnp.cumsum(probs) - probs) < top_p
    keep = keep.at[0].set(True)

    masked = jnp.where(keep, scaled, -jnp.inf)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), idx)
    rank = jax.random.categorical(key, masked)
    sampled = order[rank].astype(jnp.int32)
    return jnp.where(temp <= 0.0, greedy, sampled)


def sample_tokens(logits: jnp.ndarray, temps: jnp.ndarray,
                  top_ks: jnp.ndarray, top_ps: jnp.ndarray,
                  seeds: jnp.ndarray, idxs: jnp.ndarray) -> jnp.ndarray:
    """Sample one token per slot, in-graph.

    Args:
      logits: ``(B, V)`` float logits (one row per slot).
      temps: ``(B,)`` float temperatures; ``0`` = greedy argmax fast path
        (bit-exact).  When every lane is ``0`` the dispatch takes the
        argmax alone; otherwise every lane runs the sampling path, whose
        greedy lanes return the same argmax.
      top_ks: ``(B,)`` int top-k truncation per slot (``0`` disables).
      top_ps: ``(B,)`` float nucleus threshold per slot (``1.0`` disables).
      seeds: ``(B,)`` int per-request PRNG seeds.
      idxs: ``(B,)`` int per-request sample indices (tokens sampled so far);
        the draw uses ``fold_in(PRNGKey(seed), idx)`` so streams are
        stateless and restart-deterministic.

    Returns:
      ``(B,)`` int32 token ids.
    """
    # one scalar branch outside the vmap (a batched predicate would turn
    # the cond into a select that runs both sides); under shard_map each
    # shard decides on its own lanes
    return jax.lax.cond(
        jnp.any(temps > 0.0),
        lambda: jax.vmap(_sample_row)(logits, temps, top_ks, top_ps, seeds,
                                      idxs),
        lambda: jnp.argmax(logits, axis=-1).astype(jnp.int32))
