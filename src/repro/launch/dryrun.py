import os
os.environ["JAX_PLATFORMS"] = "cpu"     # fake host devices; never the chip
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("DRYRUN_DEVICES", "512")).strip()

"""Multi-pod dry-run: AOT lower + compile every (arch x shape) cell on the
production mesh, record memory/cost/collective analysis.

MUST be run as its own process (the XLA_FLAGS line above executes before any
jax import; jax locks device count on first init).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-moe-235b-a22b \
      --shape train_4k [--multipod] [--out results/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod]
"""
import argparse
import json
import time
import traceback

import jax
import numpy as np

from repro.configs.base import SHAPES
from repro.configs.registry import ASSIGNED, get_config
from repro.launch import hlo_analysis as ha
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (batch_shardings, cache_shardings,
                                   opt_shardings, param_shardings, replicated)
from repro.launch.specs import cache_specs, cell_is_supported, input_specs
from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step
from repro.optim.adamw import AdamWConfig, init_opt_state

#: the chip whose peaks the roofline terms are computed against (the cells
#: compile on fake host devices, so this cannot be read off the backend)
DEVICE_KIND = "TPU v5 lite"


def lower_cell(cfg, shape_name, mesh, opt_cfg=None):
    """Returns (lowered, in_info) for the cell's step function."""
    shape = SHAPES[shape_name]
    specs = input_specs(cfg, shape_name)
    p_sh = param_shardings(specs["params"], mesh)

    if shape.kind == "train":
        opt_cfg = opt_cfg or AdamWConfig()
        opt_shapes = jax.eval_shape(
            lambda p: init_opt_state(p, opt_cfg), specs["params"])
        o_sh = opt_shardings(opt_shapes, mesh)
        b_sh = batch_shardings(specs["batch"], mesh)
        step = make_train_step(cfg, opt_cfg)
        with mesh:
            lowered = jax.jit(
                step,
                in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, replicated(mesh)),
                donate_argnums=(0, 1),
            ).lower(specs["params"], opt_shapes, specs["batch"])
        return lowered

    # inference cells: TP-only params (no per-layer weight all-gathers);
    # beyond-paper distribution optimization, §Perf cell B
    p_sh = param_shardings(specs["params"], mesh, mode="inference")

    if shape.kind == "prefill":
        b_sh = batch_shardings(specs["batch"], mesh)
        step = make_prefill_step(cfg)
        with mesh:
            lowered = jax.jit(
                step, in_shardings=(p_sh, b_sh),
            ).lower(specs["params"], specs["batch"])
        return lowered

    # decode
    c_sh = cache_shardings(specs["cache"], mesh)
    t_sh = batch_shardings({"t": specs["token"]}, mesh)["t"]
    step = make_serve_step(cfg)
    with mesh:
        lowered = jax.jit(
            step,
            in_shardings=(p_sh, c_sh, t_sh, replicated(mesh)),
            out_shardings=(t_sh, c_sh),
            donate_argnums=(1,),
        ).lower(specs["params"], specs["cache"], specs["token"], specs["pos"])
    return lowered


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(mesh.devices.shape))
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
            "n_devices": n_dev}

    ok, reason = cell_is_supported(cfg, shape_name)
    if not ok:
        cell.update(status="SKIP", reason=reason)
        return cell

    t0 = time.time()
    try:
        lowered = lower_cell(cfg, shape_name, mesh)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()          # loop-UNAWARE (reference)
        hlo = compiled.as_text()
        loop_aware = hlo_cost.analyze(hlo)       # loop-aware cost model
        coll = loop_aware["coll"]

        p_specs = input_specs(cfg, shape_name)["params"]
        n_params = ha.count_params(p_specs)
        n_expert = ha.count_expert_params(p_specs)
        model_fl = ha.model_flops_estimate(cfg, shape, n_params, n_expert,
                                           shape.kind)
        roof = ha.Roofline(
            flops=loop_aware["flops"],
            hbm_bytes=loop_aware["bytes"],
            coll_bytes=coll["total"],
            n_devices=n_dev, model_flops=model_fl, device_kind=DEVICE_KIND)

        cell.update(
            status="OK", lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            n_params=n_params, n_expert_params=n_expert,
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "peak_bytes": getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0),
            },
            collectives={k: int(v) for k, v in coll.items()},
            xla_cost_raw={"flops": float(cost.get("flops", 0.0)),
                          "bytes": float(cost.get("bytes accessed", 0.0))},
            roofline=roof.to_dict(),
        )
    except Exception as e:  # noqa: BLE001
        cell.update(status="FAIL", error=f"{type(e).__name__}: {e}",
                    trace=traceback.format_exc()[-2000:])
    return cell


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = ([(a, s) for a in ASSIGNED for s in SHAPES] if args.all
             else [(args.arch, args.shape)])
    for arch, shape in cells:
        tag = "multipod" if args.multipod else "pod"
        res = run_cell(arch, shape, args.multipod, args.out)
        path = os.path.join(args.out,
                            f"{arch.replace('-', '_')}__{shape}__{tag}.json")
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        print(json.dumps({k: res[k] for k in
                          ("arch", "shape", "mesh", "status")}
                         | ({"bottleneck": res["roofline"]["bottleneck"],
                             "compile_s": res["compile_s"]}
                            if res["status"] == "OK" else
                            {"why": res.get("reason", res.get("error", ""))}),
                         ), flush=True)


if __name__ == "__main__":
    main()
