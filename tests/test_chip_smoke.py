"""``chip_smoke.py``'s one-chip body at a reduced config on the CPU, and
its refusal to run off the chip.  (The four-chip body runs on virtual
devices in ``tests/dist_progs/prog_chip_smoke_sharded.py``.)"""
import importlib.util
import json
import os

import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config

ROOT = os.path.join(os.path.dirname(__file__), os.pardir)


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_body_reduced_config(chip_smoke):
    cfg = get_config("llama3.2-3b").reduced(dtype=jnp.float32)
    res = chip_smoke.smoke(cfg, slots=4, max_seq=128,
                           prefill_chunk=16, n_requests=6,
                           prompt_lens=(10, 60), gen=8, shared_prefix=16,
                           n_shared=3, ref_len=48)
    assert set(res) == {"checks", "figures"}
    assert set(res["checks"]) == {
        "batch1_all_retired", "params_and_state_on_device",
        "decode_combine_kernel", "spec_combine_kernel",
        "batch2_compiles_nothing", "batch2_all_retired",
        "decode_logits_finite", "ref_positions_complete",
        "ref_logits_agree"}
    assert all(res["checks"].values()), res
    figs = res["figures"]
    # off the TPU the split-K combine is the in-register tree: no kernel
    assert figs["spec_tpu_custom_calls"] == 0
    assert figs["ref_positions"] == 7
    assert figs["ref_max_rel_err"] <= chip_smoke.FP32_LOGIT_RTOL
    assert figs["prefix_hits"] >= 1
    assert figs["batch2_new_executables"] == 0
    json.dumps(figs)


def test_main_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err
