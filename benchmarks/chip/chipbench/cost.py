"""Model operations, counted from shapes.

Model FLOPs count the multiply-adds of the work a run actually did (2 per
multiply-add): every weight matrix once per token processed, attention
over the keys each token saw, and the output head once per logits row
(one per decode token, one per prefill dispatch).  Prefix-reused tokens,
idle lanes and padding are not work and are not counted.  What one layer
costs is its layer kind's to say: ``layer_weights(model)`` and
``attn_per_key(model)`` in ``refs/<kind>.py``, beside the layer itself (a
layer of sparse experts counts the experts a token is routed to).
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple


def work_flops(m: Dict, kind, work: Iterable[Tuple]) -> float:
    """FLOPs of the client's work log entries ``(t, kind, tokens,
    keys summed over tokens, dispatches)`` for model ``m`` whose layers
    are of ``kind`` (a ``refs/<kind>.py`` module)."""
    n_layers, head = m["n_layers"], m["d_model"] * m["vocab"]
    per_tok = n_layers * kind.layer_weights(m)
    per_key = n_layers * kind.attn_per_key(m)
    total = 0
    for _, what, tokens, keys, dispatches in work:
        rows = dispatches if what == "prefill" else tokens
        total += tokens * per_tok + keys * per_key + rows * head
    return 2.0 * total
