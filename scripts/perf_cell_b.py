"""A/B: qwen3-moe prefill_32k at 512 chips, train-style vs inference-mode
param sharding. Writes results/perf_cell_b.json."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json, sys, time
sys.path.insert(0, "src")
import jax
from repro.configs.registry import get_config
from repro.launch import hlo_analysis as ha
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import batch_shardings, param_shardings
from repro.launch.specs import input_specs
from repro.launch.steps import make_prefill_step
from repro.configs.base import SHAPES

cfg = get_config("qwen3_moe_235b_a22b")
mesh = make_production_mesh()
specs = input_specs(cfg, "prefill_32k")
b_sh = batch_shardings(specs["batch"], mesh)
step = make_prefill_step(cfg)
out = {}
for label, mode in (("before", "train"), ("after", "inference")):
    p_sh = param_shardings(specs["params"], mesh, mode=mode)
    t0 = time.time()
    with mesh:
        compiled = jax.jit(step, in_shardings=(p_sh, b_sh)).lower(
            specs["params"], specs["batch"]).compile()
    la = hlo_cost.analyze(compiled.as_text())
    n_params = ha.count_params(specs["params"])
    n_exp = ha.count_expert_params(specs["params"])
    mf = ha.model_flops_estimate(cfg, SHAPES["prefill_32k"], n_params, n_exp,
                                 "prefill")
    roof = ha.Roofline(la["flops"], la["bytes"], la["coll"]["total"], 256, mf,
                       device_kind="TPU v5 lite")
    mem = compiled.memory_analysis()
    out[label] = {**roof.to_dict(),
                  "arg_bytes": getattr(mem, "argument_size_in_bytes", 0),
                  "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                  "compile_s": round(time.time() - t0, 1)}
    print(label, {k: round(v, 3) if isinstance(v, float) else v
                  for k, v in out[label].items() if k.startswith(("t_", "bo"))},
          flush=True)
os.makedirs("results", exist_ok=True)
json.dump({"before": out["before"], "after": out["after"]},
          open("results/perf_cell_b.json", "w"), indent=1)
