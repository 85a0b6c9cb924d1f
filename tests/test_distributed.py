"""Multi-device (8 virtual CPU devices) distributed tests.

Each program runs in a subprocess so it can set XLA_FLAGS before jax init
(the main test process keeps 1 device, per the task's dry-run isolation
rule)."""
import os
import subprocess
import sys

import pytest

PROG_DIR = os.path.join(os.path.dirname(__file__), "dist_progs")
SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


def _run(prog: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, os.path.join(PROG_DIR, prog)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"{prog} failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout


def test_radix4_collectives_and_compression():
    assert "OK collectives" in _run("prog_collectives.py")


def test_moe_expert_parallel_matches_dense():
    assert "OK moe_ep" in _run("prog_moe_ep.py")


def test_sharded_train_step_and_decode():
    assert "OK train_step" in _run("prog_train_step.py")


def test_tp_head_padding_exact():
    assert "OK head_pad" in _run("prog_head_pad.py")


def test_mesh_sharded_engine_churn_invariants():
    """2-shard engine churn walk: per-shard refcounts match the
    table+session ground truth after every op, free lists stay
    shard-resident, and no page-table row ever references a page outside
    its slot's shard block."""
    assert "OK shard churn" in _run("prog_shard_churn.py")


def test_chip_smoke_sharded_body_bit_exact():
    """chip_smoke.py --chips 4's body on 4 virtual devices: the
    mesh_shards=4 engine's tokens and logits equal the single-device
    engine's on the same seeded requests."""
    assert "OK chip smoke sharded" in _run("prog_chip_smoke_sharded.py")
