#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names a
configuration (``configs[].file``, whose ``family`` picks
``bench/families/<family>.py`` and the plain reference beside it) and a
traffic mix (``bench/traffic/<mix>.json``); ``bench/workloads/<cell>.json``
holds the cell's correctness limits, and each per-layer metric is read by
``bench/metrics/<metric>.py``. Adding a cell, a configuration of a known
family, a mix or a metric adds files and manifest entries, never an edit.

A run finds the chip (and fails without one), builds the servable from the
seed, warms up exactly the shapes the mix uses (set-up, ``setup_s``),
measures for ``--seconds`` (``--trace 1``: a window of at most the mix's
``trace_seconds`` under the profiler, reduced to the per-layer metrics),
reads the peak device memory, frees the program's state, compares a
sample of what the timed path produced with the float32 reference, and
prints one JSON line last.

``--rehearsal`` runs the same path on the CPU at the configuration's
``rehearsal`` sizes, Pallas kernels in interpret mode; its line says so and
carries no metric.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def resolve(workload: str, rehearsal: bool = False, root: str = ROOT):
    """(manifest, cell, configuration, mix, cell file) for ``workload``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    cfg = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    bench = os.path.join(root, "bench")
    with open(os.path.join(root, cfg["file"])) as f:
        conf = dict(json.load(f), name=cfg["name"])
    with open(os.path.join(bench, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    with open(os.path.join(bench, "workloads", workload + ".json")) as f:
        cellfile = json.load(f)
    if rehearsal:
        conf = _merge(conf, conf.get("rehearsal", {}))
        mix = _merge(mix, mix.get("rehearsal", {}))
        cellfile = _merge(cellfile, cellfile.get("rehearsal", {}))
    return manifest, cell, conf, mix, cellfile


def applies(metric: dict, cell: dict, manifest: dict) -> bool:
    """Whether ``metric`` is reported in ``cell``: listed there, or, with
    no list, in every cell that reports the metric it moves."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    return applies(e2e[moves], cell, manifest)


def load_reader(name: str, bench: str = BENCH):
    path = os.path.join(bench, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Run:
    """What a family's setup, window and comparison see of the run."""

    def __init__(self, conf, mix, seed, tracing=False):
        self.conf, self.mix, self.seed = conf, mix, seed
        self.tracing = tracing
        self.phases = {}
        self.log = log

    @contextlib.contextmanager
    def phase(self, name):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = self.phases.get(name, 0.0) + \
                time.perf_counter() - t

    def span(self, name):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


def check_device(chips: int, rehearsal: bool):
    """The device the run uses, or None when it may not run here."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if rehearsal:
        if dev["platform"] != "cpu":
            log("a rehearsal runs on the CPU only (JAX_PLATFORMS=cpu)")
            return None
    elif dev["platform"] != "tpu" or dev["count"] < chips:
        log(f"the cell needs {chips} TPU chip(s); JAX found {dev['count']} "
            f"{dev['platform']} device(s): no result")
        return None
    return dev


def compare_checks(nums, failed, cellfile):
    """{name: {"value", "limit"}}: each number compared, beside its limit."""
    limits = cellfile["limits"]
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    return checks


def passed(checks) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, interpret mode, rehearsal sizes; no metric")
    args = ap.parse_args(argv)
    return execute(args.workload, args.seed, args.seconds, bool(args.trace),
                   args.rehearsal)


def execute(workload, seed, seconds, trace, rehearsal):
    for p in (BENCH, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    manifest, cell, conf, mix, cellfile = resolve(workload, rehearsal)
    try:
        import jax
        from repro.runtime.compile_cache import use_compile_cache
    except ImportError as e:
        log(f"cannot import the program from {os.path.join(ROOT, 'src')}: "
            f"{e}")
        return 2
    dev = check_device(int(cell["chips"]), rehearsal)
    if dev is None:
        return 1
    cache = "off (rehearsal)"
    if not rehearsal:
        cache = use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"cell {workload}: config {cell['config']}, traffic "
        f"{cell['traffic']}, seed {seed}, device {dev['kind']} x "
        f"{dev['count']}, compile cache {cache}"
        + (" [REHEARSAL: CPU, interpret mode]" if rehearsal else ""))
    family = importlib.import_module("families." + conf["family"])
    tracing = trace and not rehearsal
    run = Run(conf, mix, seed, tracing)

    st = family.setup(run)
    gc.collect()
    gc.freeze()         # set-up's objects stay out of the window's collections
    setup_s = time.perf_counter() - T_START
    log("setup " + ", ".join(f"{k} {v:.3f} s" for k, v in
                             run.phases.items()) + f"; total {setup_s:.3f} s")

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: compiles.append(ev)
        if ev in COMPILE_EVENTS else None)
    n_compiles0 = len(compiles)
    tr = None
    if tracing:
        from lib import trace as TR
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            jax.profiler.start_trace(tdir)
            with jax.profiler.TraceAnnotation("bench.window"):
                rec = family.window(run, st,
                                    min(seconds, mix["trace_seconds"]))
            jax.profiler.stop_trace()
            t = time.perf_counter()
            devices, spans = TR.load(tdir)
            tr = TR.reduce(devices, spans, family.KERNELS, "bench.window")
            log(f"trace read and reduced in {time.perf_counter() - t:.1f} s")
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    else:
        rec = family.window(run, st, seconds)
    n_compiles = len(compiles) - n_compiles0
    stats = jax.devices()[0].memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:int(cell["chips"])])
    log(f"window {rec['window_s']:.3f} s, compilations inside it "
        f"{n_compiles}, peak device memory {peak} bytes "
        f"(limit {stats.get('bytes_limit')})")

    samples = family.collect(run, st, rec)
    del st
    gc.unfreeze()
    gc.collect()
    nums = family.compare(run, samples)
    checks = compare_checks(nums, rec["failed"], cellfile)
    ok = passed(checks)

    result = {"correct": ok, "attempted": rec["attempted"],
              "failed": rec["failed"]}
    device = dict(dev, count=jax.device_count(), memory_peak_bytes=peak)
    if rehearsal:
        result = dict({"rehearsal": True}, **result)
    else:
        metrics = {}
        if not trace:
            values = dict(family.e2e(rec), setup_s=setup_s)
            for m in manifest["end_to_end"]:
                if applies(m, cell, manifest):
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
        else:
            from lib import peaks as PK
            ctx = {"record": rec, "trace": tr, "peaks": PK.peaks(dev["kind"])}
            for m in manifest["per_layer"]:
                if applies(m, cell, manifest):
                    v = load_reader(m["name"]).read(ctx)
                    if v is not None:
                        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["metrics"] = metrics
    result["device"] = device
    if tr is not None:
        result["breakdown"] = tr["breakdown"]
    result["setup_parts_s"] = dict(run.phases)
    result["compilations_in_window"] = n_compiles
    result["checks"] = checks
    for k, v in nums.items():
        if k not in checks:
            log(f"reading {k} {v!r} (not compared)")
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
