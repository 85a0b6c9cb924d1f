"""A whole run of a cell on the CPU at a small size, past the harness's
look for a chip: set-up, pre-roll, the open-loop window and the verdict.

A sound run comes out correct, and the fp8 control, judged by the same
verdict over the same sample, comes out not correct.  With the timed path
broken underneath -- a decode step that returns its state unchanged, or a token
altered where it is produced -- ``correct`` comes out false.
"""
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, os.pardir, os.pardir, "src"))
sys.path.insert(0, HERE)

from chipbench import spec  # noqa: E402
from chipbench.cell import run_cell  # noqa: E402

#: the widest gap allowed at this size: bf16 runs read 0 to 0.008 and the
#: fp8 control about 0.3 (CPU runs of this configuration)
LIMIT = 0.05


def _cell():
    bench = spec.load_benchmark()
    model = {"family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
             "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab": 97,
             "qkv_bias": True, "rope_theta": 10000.0, "norm_eps": 1e-5, "attn_kind": "gqa",
             "dtype": "bfloat16"}
    mix = {"arrivals": {"kind": "poisson", "rate_per_s": 6.0},
           "prompt": {"mean": 24, "sigma": 0.8, "min": 4, "max": 48},
           "output": {"mean": 12, "sigma": 0.6, "min": 4, "max": 24},
           "shared_prefix": 16, "shape_seed": 0,
           "check": {"sample_requests": 4, "min_served_tokens": 20},
           "preroll_s": 1.0,
           "engine": {"max_slots": 4, "max_seq": 128, "page_size": 16,
                      "prefill_chunk": 32, "prefix_cache": True,
                      "spec_k": 0}}
    pick = lambda ms: [m for m in ms if m["name"] in
                       ("ttft_p50_ms", "itl_p95_ms", "setup_s")]
    return {"name": "tiny", "chips": 1,
            "config": {"name": "tiny", "model": model, "reference": "gqa",
                       "correct": {"max_logit_gap": LIMIT}},
            "traffic": mix, "end_to_end": pick(bench["end_to_end"]),
            "per_layer": bench["per_layer"]}


def _run(control=False):
    return run_cell(_cell(), 2 ** 31 + 17, 2.0, False,
                    t_start=time.perf_counter(), log=lambda m: None,
                    control=control)


def test_sound_run_is_correct_and_the_control_is_not():
    out = _run(control=True)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p50_ms", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["count"] >= 1
    # the control goes through the same verdict, and fails it
    cal = out["calibration"]
    assert cal["control_correct"] is False
    assert cal["max_logit_gap"] <= LIMIT < cal["control_max_gap"]


def test_decode_that_keeps_its_state_is_not_correct(monkeypatch):
    from repro.serve import ServeEngine

    inner = ServeEngine._decode_exe

    def stale(self):
        exe = inner(self)

        def run(params, state, *args):
            nxt, logits, _ = exe(params, state, *args)
            return nxt, logits, state
        return run

    monkeypatch.setattr(ServeEngine, "_decode_exe", stale)
    out = _run()
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > LIMIT


def test_token_altered_where_produced_is_not_correct(monkeypatch):
    from repro.serve.scheduler import Scheduler

    inner = Scheduler.on_decode

    def altered(self, tokens):
        first = min(tokens)
        tokens = {s: (t + 1) % 97 if s == first else t
                  for s, t in tokens.items()}
        return inner(self, tokens)

    monkeypatch.setattr(Scheduler, "on_decode", altered)
    out = _run()
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > LIMIT
