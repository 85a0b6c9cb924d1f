"""The verdict ``correct``: served tokens against the plain reference.

Once the window has closed and the program is freed, a sample of the
requests finished in the window, drawn from the seed and holding the
longest of them, is run through the reference over each prompt with its
served tokens.  Every request is greedy, so each served token should be
the reference's best up to rounding; the number compared is the widest
gap by which a served token's reference logit lies below the best
(``max_logit_gap``), against the configuration's limit.

The control (``compare(..., control=True)``) is the reference computed
in fp8 put in the program's place: the gap of the token its own logits
put first, at each position of the same prompts and served tokens.  Its
widest gap goes through the same :func:`verdict` (:func:`control_verdict`)
and has to come out not correct.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import jax.numpy as jnp
import numpy as np

from chipbench import reference as R
from chipbench import spec
from chipbench.traffic import _SEED_MOD


def sample(finished: List[Any], seed: int, k: int) -> List[Any]:
    """``k`` finished requests: the longest (prompt + served), then
    others drawn from the seed."""
    if not finished:
        return []
    size = lambda tr: len(tr.req.prompt) + len(tr.req.generated)
    longest = max(finished, key=size)
    others = [tr for tr in finished if tr is not longest]
    rng = np.random.default_rng([int(seed) % _SEED_MOD, 5])
    pick = rng.choice(len(others), size=min(k - 1, len(others)),
                      replace=False)
    return [longest] + [others[i] for i in sorted(pick)]


def pad_lengths(mix: Dict[str, Any]) -> Dict[str, int]:
    """Fixed reference shapes for a mix: rows of prompt (after the shared
    prefix) plus output, and served rows."""
    rows = mix["prompt"]["max"] + mix["output"]["max"]
    if rows > R._Q_BLOCK:
        rows = -(-rows // R._Q_BLOCK) * R._Q_BLOCK
    return {"pad_len": rows, "pad_rows": mix["output"]["max"]}


def compare(config: Dict[str, Any], mix: Dict[str, Any], seed: int,
            requests: List[Any], prefix: List[int], control: bool = False
            ) -> Dict[str, Any]:
    """Reference gaps of the sampled requests' served tokens (and the
    control's, with ``control``)."""
    t0 = time.perf_counter()
    ref = R.Reference(config["model"], spec.load_reference(
        config["reference"]), seed, jnp.bfloat16)
    pads = pad_lengths(mix)
    pasts = R.prefix_pasts(ref, prefix, False)
    cpasts = R.prefix_pasts(ref, prefix, True) if control else None
    gaps, cgaps, per_req = [], [], []
    for tr in requests:
        out = R.served_gaps(ref, tr.req.prompt, tr.req.generated,
                            prefix_len=len(prefix), pasts=pasts,
                            control_pasts=cpasts, control=control, **pads)
        gaps.append(out["gap"])
        per_req.append(float(out["gap"].max()))
        if control:
            cgaps.append(out["control_gap"])
    del ref, pasts, cpasts
    gc.collect()                     # its jitted layers hold it in a cycle
    res = {
        "requests": len(requests),
        "served_tokens": int(sum(len(g) for g in gaps)),
        "max_logit_gap": float(max(per_req)) if per_req else float("nan"),
        "per_request_max_gap": per_req,
        "reference_s": time.perf_counter() - t0,
    }
    if control:
        cg = np.concatenate(cgaps) if cgaps else np.zeros(0)
        res["control_max_gap"] = float(cg.max()) if cg.size else float("nan")
        res["control_top1_differs"] = int((cg > 0).sum())
    return res


def verdict(config: Dict[str, Any], mix: Dict[str, Any],
            cmp: Dict[str, Any]) -> Dict[str, Any]:
    """Each number compared beside its limit, and whether all hold: the
    configuration sets the gap's limit, the mix how many served tokens a
    sample must hold."""
    checks = {
        "max_logit_gap": {"value": cmp["max_logit_gap"],
                          "limit": config["correct"]["max_logit_gap"],
                          "holds": "<="},
        "served_tokens": {"value": cmp["served_tokens"],
                          "limit": mix["check"]["min_served_tokens"],
                          "holds": ">="},
    }
    ok = True
    for c in checks.values():
        v, l = c["value"], c["limit"]
        if l is None or v is None or v != v:
            ok = False
        elif c["holds"] == "<=":
            ok &= v <= l
        else:
            ok &= v >= l
    return {"correct": bool(ok), "checks": checks}


def control_verdict(config: Dict[str, Any], mix: Dict[str, Any],
                    cmp: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`verdict` of the control: its widest gap in the place of the
    program's, over the same sample."""
    return verdict(config, mix, {**cmp,
                                 "max_logit_gap": cmp["control_max_gap"]})
