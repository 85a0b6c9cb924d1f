"""CPU tests of the chip benchmark's pure parts: the benchmark file and the
files it names, the traffic generator, the operation and byte counters,
the trace reduction on a recorded fixture, the reference's weights, and
the refusal to run without a TPU."""
import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, os.pardir, os.pardir, "src"))
sys.path.insert(0, HERE)

from chipbench import cost, spec, trace  # noqa: E402
from chipbench.traffic import Traffic  # noqa: E402

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


# ------------------------------------------------------- benchmark file
def test_benchmark_file_names_existing_files():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        cfg = spec.load_config(c["name"])
        assert os.path.exists(os.path.join(spec.ROOT, c["file"]))
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key, val in cfg["reduced"].items():
            assert cfg["published"][key] != val
        assert os.path.exists(os.path.join(
            spec.BENCH_DIR, "refs", cfg["reference"] + ".py"))
    for w in BENCH["workloads"]:
        cell = spec.resolve_cell(BENCH, w["name"])
        assert cell["end_to_end"] and cell["per_layer"]
        assert "setup_s" in [m["name"] for m in cell["end_to_end"]]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(spec.metric_reader(m["name"]))


def test_per_layer_metrics_list_cells_reporting_what_they_move():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in moved.get("workloads", cells), (m["name"], w)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_sizes_fit_the_engine(workload):
    cell = spec.resolve_cell(BENCH, workload)
    mix, eng = cell["traffic"], cell["traffic"]["engine"]
    longest = (mix.get("shared_prefix", 0) + mix["prompt"]["max"]
               + mix["output"]["max"])
    assert longest <= eng["max_seq"]
    assert eng["max_seq"] % eng["page_size"] == 0


# --------------------------------------------------------------- traffic
def _mix(kind="poisson", **arr):
    return {"arrivals": dict(kind=kind, **arr),
            "prompt": {"mean": 64, "sigma": 0.8, "min": 4, "max": 200},
            "output": {"mean": 16, "sigma": 0.6, "min": 2, "max": 64},
            "shared_prefix": 0, "n_requests": 400, "shape_seed": 0}


def test_traffic_is_a_function_of_the_seed():
    big = 2 ** 31 + 12345
    a, b = Traffic(_mix(rate_per_s=2.0), big, 1000, 5.0, 195.0), \
        Traffic(_mix(rate_per_s=2.0), big, 1000, 5.0, 195.0)
    assert [(x.t, x.prompt_len, x.max_new) for x in a.arrivals] == \
        [(x.t, x.prompt_len, x.max_new) for x in b.arrivals]
    assert a.prompt(a.arrivals[3]) == b.prompt(b.arrivals[3])


def test_seeds_reorder_one_multiset_of_work():
    a = Traffic(_mix(rate_per_s=2.0), 1, 1000, 5.0, 195.0)
    b = Traffic(_mix(rate_per_s=2.0), 2, 1000, 5.0, 195.0)
    sizes = lambda t: sorted((x.prompt_len, x.max_new) for x in t.arrivals)
    gaps = lambda t: sorted(np.round(np.diff(
        [-5.0] + [x.t for x in t.arrivals]), 9))
    assert sizes(a) == sizes(b) and gaps(a) == gaps(b)
    assert [x.prompt_len for x in a.arrivals] != \
        [x.prompt_len for x in b.arrivals]
    assert a.prompt(a.arrivals[0]) != b.prompt(b.arrivals[0])


def test_poisson_rate_and_lengths():
    t = Traffic(_mix(rate_per_s=4.0), 7, 1000, 2.0, 98.0)
    times = np.array([x.t for x in t.arrivals])
    assert times[0] >= -2.0 and np.all(np.diff(times) > 0)
    # 4/s over 2 s of pre-roll and a 98 s window: 400 arrivals, the last
    # at the window's end
    assert len(times) == 400 and math.isclose(times[-1], 98.0)
    plens = np.array([x.prompt_len for x in t.arrivals])
    assert plens.min() >= 4 and plens.max() <= 200
    assert 50 < plens.mean() < 75


def test_square_wave_bursts_and_mean_rate():
    t = Traffic(_mix("square_wave", rate_per_s=2.0, burst_factor=4.0,
                     burst_fraction=0.25, period_s=4.0), 3, 1000, 0.0,
                     200.0)
    times = np.array([x.t for x in t.arrivals])
    assert abs(len(times) / times[-1] - 2.0) < 0.3
    in_burst = np.mean((times % 4.0) < 1.0)
    # a quarter of the time at 4x the base rate: 4/7 of the arrivals
    assert abs(in_burst - 4 / 7) < 0.07


@pytest.mark.parametrize("name", ["chat", "agent"])
@pytest.mark.parametrize("seconds", [10.0, 51.0])
def test_timed_mixes_fill_the_window_they_are_run_for(name, seconds):
    mix = spec.load_traffic(name)
    pre, rate = mix["preroll_s"], mix["arrivals"]["rate_per_s"]
    t = Traffic(mix, 2 ** 31 + 5, 1000, pre, seconds)
    assert len(t.arrivals) == round(rate * (pre + seconds))
    last = max(x.t for x in t.arrivals)
    # the arrivals end within about half a mean gap of the window's end
    assert abs(last - seconds) < 1.0 / rate


def test_backlog_and_shared_prefix():
    mix = _mix("backlog", depth=8)
    mix["shared_prefix"] = 32
    t = Traffic(mix, 5, 1000, 1.0, 10.0)
    assert len(t.arrivals) == 400
    assert all(x.t is None for x in t.arrivals)
    p0, p1 = t.prompt(t.arrivals[0]), t.prompt(t.arrivals[1])
    assert p0[:32] == p1[:32] == t.prefix and p0[32:] != p1[32:]
    assert len(p0) == 32 + t.arrivals[0].prompt_len


# ----------------------------------------------------------------- costs
def test_layer_counts_by_hand_glm4():
    m = spec.load_config("glm4-9b")["model"]
    gqa = spec.load_reference("gqa")
    # q 4096x4096, k and v 4096x256 each, o 4096x4096, SwiGLU 3x4096x13696
    attn = 4096 * 4096 * 2 + 4096 * 256 * 2
    assert gqa.layer_weights(m) == attn + 3 * 4096 * 13696
    assert gqa.attn_per_key(m) == 2 * 32 * 128
    # one layer ~ 204 M parameters, 20 of them in the stage
    assert abs(gqa.layer_weights(m) - 204e6) < 1e6


def test_layer_counts_by_hand_minicpm3():
    m = spec.load_config("minicpm3-4b")["model"]
    mla = spec.load_reference("mla")
    q = 2560 * 768 + 768 * 40 * 96
    kv = 2560 * (256 + 32) + 256 * 40 * (64 + 64)
    o = 40 * 64 * 2560
    assert mla.layer_weights(m) == q + kv + o + 3 * 2560 * 6400
    assert mla.attn_per_key(m) == 40 * (64 + 32 + 64)


@pytest.mark.parametrize("kind", ["gqa", "mla"])
def test_weight_count_matches_the_reference_layout(kind):
    """A dense layer's multiply-adds per token are its weight matrices'
    sizes, as the reference lays them out."""
    name = {"gqa": "glm4-9b", "mla": "minicpm3-4b"}[kind]
    m = spec.load_config(name)["model"]
    ref = spec.load_reference(kind)
    mats = []

    def walk(t):
        if isinstance(t, dict):
            for v in t.values():
                walk(v)
        elif len(t[0]) == 2:
            mats.append(t[0][0] * t[0][1])
    walk(ref.block_layout(m))
    assert ref.layer_weights(m) == sum(mats)


def test_unknown_layer_kind_is_an_error():
    with pytest.raises(KeyError, match="no reference"):
        spec.load_reference("moe_gqa")


def test_work_flops_counts_work_done():
    m = spec.load_config("glm4-9b")["model"]
    gqa = spec.load_reference("gqa")
    per_tok = 2 * 20 * gqa.layer_weights(m)
    head = 2 * 4096 * 151552
    per_key = 2 * 20 * gqa.attn_per_key(m)
    # a 4-token prefill in one dispatch (keys 1+2+3+4), then a decode
    # step of 3 live lanes at positions 9, 19, 29 (keys 10+20+30)
    work = [(0.0, "prefill", 4, 10, 1), (0.1, "decode", 3, 60, 1)]
    want = 7 * per_tok + 70 * per_key + (1 + 3) * head
    assert math.isclose(cost.work_flops(m, gqa, work), want)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks_for("cpu")


# ----------------------------------------------------------------- trace
#: the kernels the benchmark's per-layer metrics read from a trace
KERNELS = spec.metric_kernels(BENCH["per_layer"])


def test_kernel_metrics_name_their_kernel():
    assert KERNELS == {"moa_reduce": "jit_decode"}


def test_trace_reduction_on_the_recorded_fixture():
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as f:
        ev = json.load(f)
    red = trace.reduce(ev, KERNELS)
    us = 1e-6
    assert math.isclose(red["window_s"], 1000 * us)
    # busy: [100,250] + [260,400] + [500,700] + [800,950]; the op at 1200
    # lies outside the window
    assert math.isclose(red["busy_s"], 640 * us)
    assert red["modules"]["jit_decode"][0] == 2
    assert math.isclose(red["modules"]["jit_decode"][1], 450 * us)
    assert red["modules"]["jit_prefill"][0] == 1
    moa = red["kernels"]["moa_reduce"]
    assert math.isclose(moa["s"], 150 * us)
    assert math.isclose(moa["in_module_s"], 100 * us)
    assert moa["module_runs"] == 2
    # a kernel counted inside another executable
    other = trace.reduce(ev, {"moa_reduce": "jit_prefill"})
    assert math.isclose(other["kernels"]["moa_reduce"]["in_module_s"],
                        50 * us)
    assert other["kernels"]["moa_reduce"]["module_runs"] == 1
    idle = red["idle_by_span"]
    want = {"decode": 80, "step": 90, "admit": 60, "wait": 70, "other": 60}
    assert set(idle) == set(want)
    for k, v in want.items():
        assert math.isclose(idle[k], v * us, rel_tol=1e-9, abs_tol=1e-12)


def test_trace_readers_on_the_fixture():
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as f:
        red = trace.reduce(json.load(f), KERNELS)
    m = spec.load_config("glm4-9b")["model"]
    peaks = spec.peaks_for("TPU v5 lite")
    rec = {"trace": red, "traced": {"flops": 1e8}, "peaks": peaks,
           "model": m}
    idle = spec.metric_reader("device_idle_frac.online")(rec)
    assert math.isclose(idle, 36.0)
    mfu = spec.metric_reader("mfu.online")(rec)
    assert math.isclose(mfu, 100 * 1e8 / (1e-3 * 197e12))
    kernel = spec.metric_reader("moa_reduce_ms")(rec)
    assert math.isclose(kernel, 1e3 * 100e-6 / 2)
    no_kernel = dict(red, kernels={})
    assert spec.metric_reader("moa_reduce_ms")(
        dict(rec, trace=no_kernel)) is None
    assert spec.metric_reader("moa_reduce_ms")(dict(rec, trace=None)) is None


def test_trace_reduction_on_a_recorded_chip_trace():
    """25 ms of a traced ``glm4-9b.chat`` run on a TPU v5e: busy time
    agrees with a union taken on a 0.1 us grid, the idle time is all laid
    against host spans, and the combine kernel is found in a decode run."""
    with open(os.path.join(HERE, "fixtures", "trace_v5e_chat.json")) as f:
        ev = json.load(f)
    red = trace.reduce(ev, KERNELS)
    assert math.isclose(red["window_s"], 0.025)
    base = [s for n, s, d in ev["host"] if n == trace.WINDOW_SPAN][0]
    grid = np.zeros(250_000, bool)
    for _, s, d in ev["devices"]["0"]["ops"]:
        lo = max(0, int((s - base) // 100))
        hi = min(grid.size, int(-(-(s + d - base) // 100)))
        grid[lo:hi] = True
    assert abs(red["busy_s"] - grid.sum() * 1e-7) < 0.01 * red["busy_s"]
    assert 0 < red["busy_s"] <= red["window_s"]
    moa = red["kernels"]["moa_reduce"]
    assert moa["module_runs"] >= 1
    assert 0 < moa["in_module_s"] <= moa["s"]
    assert moa["in_module_s"] <= red["modules"]["jit_decode"][1]
    idle = red["window_s"] - red["busy_s"]
    assert math.isclose(sum(red["idle_by_span"].values()), idle,
                        rel_tol=1e-6, abs_tol=1e-9)


def test_trace_without_window_reads_nothing():
    assert trace.reduce({"devices": {}, "host": []}, KERNELS) is None


# ------------------------------------------------------------- reference
def test_reference_weights_equal_the_served_weights():
    """The reference draws its weights itself; at a small size they are
    bit-equal to the serve path's (the test alone imports both)."""
    import jax
    import jax.numpy as jnp
    from chipbench import reference as R
    from chipbench.cell import model_config
    from repro.launch.serve import init_serve_params

    for kind, model in (("gqa", dict(
            family="dense", n_layers=2, d_model=32, n_heads=4,
            n_kv_heads=2, head_dim=8, d_ff=48, vocab=53, qkv_bias=True,
            rope_theta=1e4, norm_eps=1e-5, attn_kind="gqa",
            dtype="bfloat16")), ("mla", dict(
            family="dense", n_layers=2, d_model=32, n_heads=4,
            n_kv_heads=4, head_dim=12, d_ff=48, vocab=53, qkv_bias=False,
            rope_theta=1e4, norm_eps=1e-5, attn_kind="mla",
            q_lora_rank=16, kv_lora_rank=8,
            qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, dtype="bfloat16"))):
        config = {"name": "t-" + kind, "model": model}
        seed = 2 ** 31 + 99
        served = init_serve_params(model_config(config), seed)
        ref = R.init_weights(R.model_layout(
            model, spec.load_reference(kind).block_layout(model)), seed,
            jnp.bfloat16)
        flat_s = jax.tree_util.tree_flatten_with_path(served)[0]
        flat_r = dict(jax.tree_util.tree_flatten_with_path(ref)[0])
        assert len(flat_s) == len(flat_r)
        for path, leaf in flat_s:
            assert np.array_equal(np.asarray(leaf),
                                  np.asarray(flat_r[path])), path


def test_model_config_takes_every_field_from_the_file():
    from chipbench.cell import model_config

    model = dict(spec.load_config("glm4-9b")["model"], family="moe",
                 n_experts=8, top_k=2)
    cfg = model_config({"name": "glm-moe", "model": model})
    assert (cfg.family, cfg.n_experts, cfg.top_k) == ("moe", 8, 2)
    assert cfg.n_kv_heads == 2 and cfg.arch_id == "glm-moe"


# ---------------------------------------------------------------- refusal
def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


# ----------------------------------------------------- adding by files only
def test_a_cell_is_added_by_new_files_and_entries(tmp_path, monkeypatch):
    """A new configuration (with a layer kind of its own), traffic mix and
    per-layer metrics (one of them a kernel's): new files and new entries
    in BENCHMARK.json, and no existing file edited."""
    import shutil

    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(spec.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    cfg = spec.load_config("minicpm3-4b")
    cfg["name"] = "minicpm3-4b-half"

    mix = spec.load_traffic("chat")
    mix["arrivals"]["rate_per_s"] = 0.25
    (bench_dir / "traffic" / "chat_slow.json").write_text(json.dumps(mix))
    (bench_dir / "metrics" / "preemptions.py").write_text(
        "def read(rec):\n    return rec['counters'].get('preemptions')\n")
    # a kernel's metric names its kernel and executable in its own file
    (bench_dir / "metrics" / "fusion4_ms.py").write_text(
        "KERNEL = ('fusion.4', 'jit_prefill')\n\n\ndef read(rec):\n"
        "    return 1e3 * rec['trace']['kernels']['fusion.4']['s']\n")
    # a layer kind brings its reference layer and its operation counts
    shutil.copy(bench_dir / "refs" / "mla.py", bench_dir / "refs" / "mla2.py")
    cfg["reference"] = "mla2"
    (bench_dir / "configs" / "minicpm3-4b-half.json").write_text(
        json.dumps(cfg))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(bench["configs"][1],
                                 name="minicpm3-4b-half"))
    bench["workloads"].append({"name": "minicpm3-4b-half.chat_slow",
                               "config": "minicpm3-4b-half",
                               "traffic": "chat_slow", "chips": 1,
                               "why": "a cell added by files alone"})
    for m in bench["end_to_end"]:
        if m["name"] == "ttft_p50_ms":
            m["workloads"].append("minicpm3-4b-half.chat_slow")
    bench["per_layer"].append({
        "name": "preemptions", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "cache manager",
        "moves": "ttft_p50_ms", "workloads": ["minicpm3-4b-half.chat_slow"]})
    bench["per_layer"].append({
        "name": "fusion4_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "ttft_p50_ms", "workloads": ["minicpm3-4b-half.chat_slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(spec, "BENCH_DIR", bench_dir)
    cell = spec.resolve_cell(spec.load_benchmark(tmp_path),
                             "minicpm3-4b-half.chat_slow")
    assert cell["traffic"]["arrivals"]["rate_per_s"] == 0.25
    assert cell["config"]["name"] == "minicpm3-4b-half"
    assert "preemptions" in [m["name"] for m in cell["per_layer"]]
    assert spec.metric_reader("preemptions")(
        {"counters": {"preemptions": 3}}) == 3
    kernels = spec.metric_kernels(cell["per_layer"])
    assert kernels == {"fusion.4": "jit_prefill"}
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as f:
        red = trace.reduce(json.load(f), kernels)
    assert spec.metric_reader("fusion4_ms")({"trace": red}) > 0
    kind = spec.load_reference(cell["config"]["reference"])
    m = cell["config"]["model"]
    assert cost.work_flops(m, kind, [(0.0, "decode", 1, 1, 1)]) > 0
    for p, data in before.items():
        assert p.read_bytes() == data, p
