"""Recompute model_flops / ratios in existing dryrun JSONs after the
count_expert_params fix (no recompilation: HLO-derived terms are unchanged).
"""
import glob
import json
import os
import sys

sys.path.insert(0, "src")

from repro.configs.base import SHAPES
from repro.configs.registry import get_config
from repro.launch import hlo_analysis as ha
from repro.launch.specs import params_specs

cache = {}
for path in sorted(glob.glob(sys.argv[1] if len(sys.argv) > 1
                             else "results/dryrun/*.json")):
    cell = json.load(open(path))
    if cell.get("status") != "OK":
        continue
    arch = cell["arch"]
    if arch not in cache:
        cfg = get_config(arch)
        p = params_specs(cfg)
        cache[arch] = (cfg, ha.count_params(p), ha.count_expert_params(p))
    cfg, n_params, n_expert = cache[arch]
    shape = SHAPES[cell["shape"]]
    mf = ha.model_flops_estimate(cfg, shape, n_params, n_expert, shape.kind)
    r = cell["roofline"]
    roof = ha.Roofline(r["flops_per_dev"], r["hbm_bytes_per_dev"],
                       r["coll_bytes_per_dev"], r["n_devices"], mf,
                       device_kind=r.get("device_kind", "TPU v5 lite"))
    cell["n_params"], cell["n_expert_params"] = n_params, n_expert
    cell["roofline"] = roof.to_dict()
    json.dump(cell, open(path, "w"), indent=1)
    print(f"{os.path.basename(path):55s} useful={roof.useful_flop_ratio:.3f} "
          f"frac={roof.roofline_fraction:.4f}")
