#!/usr/bin/env python3
"""Readings for the correctness limits: the program and its control.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10 \
        [--rehearsal]

For each seed, in one process: the cell's set-up and a window at its own
load, then the numbers that ``bench/run.py`` compares, read two ways on the
same sampled requests:

  * ``program``: what the timed path produced, against the float32
    reference (the lower reading of each limit);
  * ``control``: the reference itself computed with float8 weights and
    activations (W8A8) put in the program's place (the upper reading).

Each arm's numbers go through the run's own comparison with the cell's
limits: one JSON line per seed and arm, with ``correct`` and the checks.
The control has to come out not correct. The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run as R   # noqa: E402  (bench/run.py)

#: the precision below the configuration's bfloat16 (references/common.py)
CONTROL = "fp8"


def readings(workload, seed, seconds, rehearsal):
    """{arm: {"correct", "checks", "numbers"}} for ``program`` and
    ``control`` on one seed."""
    _, cell, conf, mix, cellfile = R.resolve(workload, rehearsal)
    family = importlib.import_module("families." + conf["family"])
    run = R.Run(conf, mix, seed)
    st = family.setup(run)
    rec = family.window(run, st, seconds)
    samples = family.collect(run, st, rec)
    del st
    gc.collect()
    out = {}
    for arm, quant in (("program", None), ("control", CONTROL)):
        nums = family.compare(run, samples, quant=quant)
        checks = R.compare_checks(nums, rec["failed"], cellfile)
        out[arm] = {"correct": R.passed(checks), "checks": checks,
                    "numbers": nums}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    _, cell, _, _, _ = R.resolve(args.workload, args.rehearsal)
    if R.check_device(int(cell["chips"]), args.rehearsal) is None:
        return 1
    if not args.rehearsal:
        import jax
        from repro.runtime.compile_cache import use_compile_cache
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for seed in (int(s) for s in args.seeds.split(",")):
        for arm, r in readings(args.workload, seed, args.seconds,
                               args.rehearsal).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "arm": arm, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
