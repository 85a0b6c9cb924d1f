import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import: jax locks the device count on first init.
# The dry-run is the ONLY entry point that sees 512 placeholder devices.
# It is a fake-mesh tool, so it stays on the CPU: on a TPU host this
# process (and each --isolate child, which inherits the environment) then
# never takes the chip.

import argparse
import json
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, ModelConfig, ShapeConfig, cells_for
from repro.configs.registry import ARCHS, get_config, list_archs
from repro.launch.inputs import (batch_logical_axes, batch_spec_shapes,
                                 decode_state_structs, input_specs)
from repro.launch.mesh import make_production_mesh, mesh_summary
from repro.models.common import (logical_to_pspec, make_shardings,
                                 param_count, shape_structs, unrolled_scans)
from repro.models.registry import get_api
from repro.optim.adamw import AdamWConfig
from repro.roofline.analysis import (V5E, collective_breakdown, extract_cost,
                                     fmt_seconds, model_flops,
                                     roofline_report)
from repro.train.state import (build_train_step, train_state_shardings,
                               train_state_specs)

__all__ = ["run_cell", "main"]


def _active_params(cfg: ModelConfig, n_params: int) -> int:
    """Activated parameter count (MoE: top_k of n_experts expert params)."""
    if not cfg.n_experts:
        return n_params
    api = get_api(cfg)
    specs = api.param_specs(cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: hasattr(x, "axes"))
    expert, rest = 0, 0
    for path, s in flat:
        n = int(np.prod(s.shape))
        if "experts" in s.axes:
            expert += n
        else:
            rest += n
    return rest + expert * cfg.top_k // cfg.n_experts


def _batch_shardings(cfg, shape, mesh):
    ax = batch_logical_axes(cfg, shape)
    shp = batch_spec_shapes(cfg, shape)
    return {k: NamedSharding(mesh,
                             logical_to_pspec(ax[k], mesh, None, shp[k][0]))
            for k in ax}


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               donate: bool = True):
    """(jitted fn, arg structs tuple) for one (arch x shape) cell."""
    api = get_api(cfg)
    if shape.kind == "train":
        opt = AdamWConfig(lr=1e-4, grad_clip=1.0)
        step = build_train_step(cfg, opt, mesh)
        state_structs = shape_structs(train_state_specs(cfg))
        in_sh = (train_state_shardings(cfg, mesh),
                 _batch_shardings(cfg, shape, mesh))
        fn = jax.jit(step, in_shardings=in_sh,
                     donate_argnums=(0,) if donate else ())
        return fn, (state_structs, input_specs(cfg, shape))
    if shape.kind == "prefill":
        def prefill(params, batch):
            out = api.forward(params, batch, cfg, mesh)
            return out[0] if isinstance(out, tuple) else out
        pspecs = api.param_specs(cfg)
        in_sh = (make_shardings(pspecs, mesh),
                 _batch_shardings(cfg, shape, mesh))
        fn = jax.jit(prefill, in_shardings=in_sh)
        return fn, (shape_structs(pspecs), input_specs(cfg, shape))
    # decode: one new token against a seq_len-deep cache
    def serve_step(params, state, batch):
        return api.decode_step(params, state, batch, cfg, mesh)
    pspecs = api.param_specs(cfg)
    sstructs, sspecs = decode_state_structs(cfg, shape)
    in_sh = (make_shardings(pspecs, mesh), make_shardings(sspecs, mesh),
             _batch_shardings(cfg, shape, mesh))
    fn = jax.jit(serve_step, in_shardings=in_sh,
                 donate_argnums=(1,) if donate else ())
    return fn, (shape_structs(pspecs), sstructs, input_specs(cfg, shape))


def _sharded_bytes(structs, shardings, mesh) -> float:
    """Analytic per-device resident bytes for a struct tree under shardings."""
    total = 0.0
    for s, sh in zip(jax.tree.leaves(structs), jax.tree.leaves(shardings)):
        n = int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
        spec = sh.spec
        div = 1
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                div *= mesh.shape[a]
        total += n / div
    return total


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
             mesh=None, hw=V5E, verbose: bool = True,
             cost_pass: bool = True, cfg: Optional[ModelConfig] = None,
             ) -> Dict[str, Any]:
    cfg = cfg if cfg is not None else get_config(arch_id)
    shape = SHAPES[shape_name]
    mesh = mesh if mesh is not None else make_production_mesh(
        multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.monotonic()
    fn, structs = build_cell(cfg, shape, mesh)
    # pass 1 — production lowering (scan over layers): the compile-proof +
    # memory analysis. HLO is O(1) in depth.
    with mesh:
        lowered = fn.lower(*structs)
        t_lower = time.monotonic() - t0
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower

    # pass 2 — cost lowering with every model scan unrolled: XLA's
    # HloCostAnalysis counts while bodies ONCE, so the production module
    # undercounts FLOPs/bytes by the trip counts. The unrolled module is
    # trip-complete; ``lowered.cost_analysis()`` (no compile — sub-second
    # even for the 26B arch) yields GLOBAL pre-partitioning numbers, which
    # we divide by the chip count. Collectives come from the PRODUCTION
    # compiled HLO with while-trip expansion (see roofline.analysis), so
    # they are per-device and partitioner-true.
    t1 = time.monotonic()
    if cost_pass:
        fn2, structs2 = build_cell(cfg, shape, mesh, donate=False)
        with mesh:
            with unrolled_scans():
                lowered_c = fn2.lower(*structs2)
        cost_global = extract_cost(lowered_c)
    else:
        cost_global = extract_cost(lowered)
    t_cost = time.monotonic() - t1

    cost = {"flops": cost_global["flops"] / chips,
            "bytes": cost_global["bytes"] / chips,
            "flops_global": cost_global["flops"],
            "bytes_global": cost_global["bytes"]}
    hlo = compiled.as_text()
    coll = collective_breakdown(hlo)
    coll_bytes = sum(v["bytes"] for v in coll.values())

    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_size": getattr(mem, "argument_size_in_bytes", None),
            "output_size": getattr(mem, "output_size_in_bytes", None),
            "temp_size": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:                                # pragma: no cover
        mem_info = {"error": str(e)}

    # analytic per-device residency (params/opt/cache under their shardings)
    api = get_api(cfg)
    if shape.kind == "train":
        res_bytes = _sharded_bytes(structs[0],
                                   train_state_shardings(cfg, mesh), mesh)
    else:
        pspecs = api.param_specs(cfg)
        res_bytes = _sharded_bytes(shape_structs(pspecs),
                                   make_shardings(pspecs, mesh), mesh)
        if shape.kind == "decode":
            _, sspecs = decode_state_structs(cfg, shape)
            res_bytes += _sharded_bytes(shape_structs(sspecs),
                                        make_shardings(sspecs, mesh), mesh)

    n_params = param_count(api.param_specs(cfg))
    n_active = _active_params(cfg, n_params)
    n_tokens = (shape.global_batch * shape.seq_len
                if shape.kind in ("train", "prefill") else shape.global_batch)
    mf = model_flops(n_params, n_tokens, shape.kind, n_active)

    roof = roofline_report(
        flops_per_device=cost["flops"], bytes_per_device=cost["bytes"],
        coll_bytes_per_device=coll_bytes, chips=chips, hw=hw,
        model_flops_total=mf)

    rec = {
        "arch": arch_id, "shape": shape_name, "kind": shape.kind,
        "mesh": mesh_summary(mesh), "chips": chips,
        "multi_pod": multi_pod,
        "n_params": n_params, "n_active_params": n_active,
        "tokens_per_step": n_tokens,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "cost_pass_s": round(t_cost, 2), "cost_pass_unrolled": cost_pass,
        "cost_analysis": cost,
        "collectives": coll,
        "collective_bytes_per_device": coll_bytes,
        "memory_analysis": mem_info,
        "resident_bytes_per_device": res_bytes,
        "fits_hbm": res_bytes < hw.hbm_per_chip,
        "roofline": roof,
    }
    if verbose:
        print(f"[dryrun] {arch_id:24s} {shape_name:12s} mesh={rec['mesh']:28s}"
              f" compile={t_compile:6.1f}s"
              f" flops/dev={cost['flops']:.3e}"
              f" coll/dev={coll_bytes:.3e}B"
              f" resident/dev={res_bytes / 1e9:.2f}GB"
              f" dominant={roof['dominant']}"
              f" bound={fmt_seconds(roof['bound_s'])}")
        sys.stdout.flush()
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="multi-pod AOT dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--isolate", action="store_true",
                    help="run each cell in a subprocess (XLA CHECK-crash "
                         "containment)")
    ap.add_argument("--print-hlo-collectives", action="store_true")
    args = ap.parse_args(argv)

    archs = list_archs() if args.arch == "all" else [args.arch]
    os.makedirs(args.out, exist_ok=True)

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = []
    meshes_cache = {}
    for mp in meshes:
        meshes_cache[mp] = make_production_mesh(multi_pod=mp)
    for arch_id in archs:
        cfg = get_config(arch_id)
        shapes = (cells_for(arch_id, cfg.encoder_only)
                  if args.shape == "all" else [args.shape])
        for shape_name in shapes:
            tag = f"{arch_id}__{shape_name}"
            for mp in meshes:
                mesh_tag = "multi" if mp else "single"
                fname = os.path.join(args.out, f"{tag}__{mesh_tag}.json")
                if os.path.exists(fname) and not args.force:
                    print(f"[dryrun] skip (cached) {fname}")
                    continue
                if args.isolate:
                    import subprocess
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch_id, "--shape", shape_name,
                           "--mesh", mesh_tag, "--out", args.out]
                    r = subprocess.run(cmd, capture_output=True, text=True,
                                       timeout=3600)
                    sys.stdout.write(
                        "\n".join(l for l in r.stdout.splitlines()
                                  if l.startswith("[dryrun]")) + "\n")
                    sys.stdout.flush()
                    if r.returncode != 0:
                        tailerr = (r.stderr or r.stdout)[-400:]
                        failures.append((tag, mp, tailerr))
                        print(f"[dryrun] FAIL (subprocess) {tag} "
                              f"multi_pod={mp}")
                    continue
                try:
                    rec = run_cell(arch_id, shape_name, multi_pod=mp,
                                   mesh=meshes_cache[mp])
                    with open(fname, "w") as f:
                        json.dump(rec, f, indent=1)
                except Exception as e:
                    failures.append((tag, mp, repr(e)[:500]))
                    print(f"[dryrun] FAIL {tag} multi_pod={mp}: "
                          f"{repr(e)[:300]}")
                    sys.stdout.flush()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, mp, err in failures:
            print(f"  {tag} multi_pod={mp}: {err}")
        return 1
    print("\nAll dry-run cells compiled.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
