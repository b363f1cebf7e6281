"""Kernel-level microbenchmark: dense vs sparse backends on CPU wall-clock
across densities, at the BERT projection shape (768x768) and the FFN shape
(3072x768). Shows where each sparse path's crossover density sits on this
backend -- the kernel-level version of Table 1.

Backends swept (see src/repro/kernels/ops.py and docs/PERF.md):
  * gather  -- one gather per stored tile (pure-XLA baseline);
  * rowpack -- row-grouped batched matmul, data scattered per call;
  * plan    -- precomputed RowPackPlan, data stored row-grouped offline
               (the serving path of repro/serving/export.py).

Besides the default (32, 32) kernel tile, the sweep includes the paper's
32x1 linear sparsity block at serving densities.

Output CSV: name,us_per_call,derived  (derived = speedup vs dense); the same
records are persisted to BENCH_kernels.json at the repo root (section
"kernel") so future PRs have a perf trajectory to compare against.

Every cell also runs the serving autotuner (kernels/autotune.py,
``ServingSpec backend='auto'``) over the same candidate set and records
whether its pick lands within 5% of the cell's measured best -- the
"measure, don't assume" check of the Sparsity Roofline argument, persisted
as the "autotune" section. With REPRO_AUTOTUNE_STUB=1 (CI) the pick comes
from the deterministic proxy instead of wall clocks; the section then
records mode="stub" and the 5% flag is informational only.

Run:  PYTHONPATH=src python benchmarks/kernel_bench.py [--smoke] [--no-json]
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparsity import prune_to_sparsity
from repro.kernels import pack_bsr
from repro.kernels.autotune import choose_backend
from repro.kernels.exec_plan import (pack_plan_data, plan_for_pack,
                                     plan_linear, plan_linear_pallas)
from repro.kernels.flash_decode import flash_decode
from repro.kernels.ops import bsr_linear
from repro.models.attention import decode_attention
from repro.runtime.bench_io import update_bench_json
from repro.runtime.compile_cache import use_compile_cache

SHAPES = [("proj_768", 768, 768), ("ffn_3072", 3072, 768)]
DENSITIES = (1.0, 0.5, 0.2, 0.1, 0.05)
M = 384
SQUARE_TILE = (32, 32)
LINEAR_TILE = (32, 1)          # the paper's end-to-end CPU-optimal block
LINEAR_DENSITIES = (0.2, 0.1)  # serving regime only (nnzt is large at 32x1)
BACKENDS = ("gather", "rowpack", "plan")


def _time_group(fns_args, reps=7):
    """Paired timing: interleave the reps of all contestants round-robin so
    machine drift (shared cores, thermal) hits every arm equally -- backend
    *ordering* is then trustworthy even when absolute times wander. Returns
    ``(mins, scores)``: min-of-reps per contestant (scheduler noise on a
    shared box is one-sided: it only slows a run down, so the minimum
    approximates the quiet-machine time) and the median paired ratio vs
    the first contestant (each round's arms see the same machine state --
    the drift-robust *ordering* statistic, same one the autotuner ranks
    by)."""
    for fn, args in fns_args:
        jax.block_until_ready(fn(*args))        # compile + warm
    ts = [[] for _ in fns_args]
    for _ in range(reps):
        for i, (fn, args) in enumerate(fns_args):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts[i].append(time.perf_counter() - t0)
    anchor = np.asarray(ts[0], np.float64)
    scores = [float(np.median(np.asarray(t, np.float64) / anchor))
              for t in ts]
    return [float(np.min(t)) for t in ts], scores


def _sparse_fn(pk, backend):
    """Jitted callable + its data argument for one (pattern, backend)."""
    if backend == "plan":
        plan = plan_for_pack(pk)
        data = pack_plan_data(plan, pk.data)
        return jax.jit(lambda x_, d_, _p=plan: plan_linear(x_, d_, _p)), data
    return (jax.jit(lambda x_, d_, _pk=pk, _b=backend:
                    bsr_linear(x_, d_, _pk, _b)), pk.data)


def run(emit=print, smoke=False, write_json=True, reps=7):
    """Sweep backends; returns the record list written to BENCH_kernels.json.

    ``smoke`` restricts to one serving density at the default tile with
    fewer reps -- the ~30 s CI smoke of scripts/check.sh.
    """
    rng = np.random.RandomState(0)
    if smoke:
        sweeps = [(SQUARE_TILE, (0.2,))]
        reps = min(reps, 3)
    else:
        sweeps = [(SQUARE_TILE, DENSITIES), (LINEAR_TILE, LINEAR_DENSITIES)]
    records = []
    auto_records = []
    for name, n, k in SHAPES:
        x = jnp.asarray(rng.randn(M, k).astype(np.float32))
        w = jnp.asarray(rng.randn(n, k).astype(np.float32))
        dense = jax.jit(lambda x_, w_: x_ @ w_.T)
        for tile, densities in sweeps:
            tile_tag = "" if tile == SQUARE_TILE else \
                f"_t{tile[0]}x{tile[1]}"
            # at the 32x1 tile nnzt explodes and the gather path would
            # materialize an (nnzt, M, bn) product (~0.7 GB at the FFN
            # shape) -- exactly the docs/PERF.md point about aggregating
            # small sparsity blocks into kernel tiles; sweep the
            # row-grouped backends only there
            backends = BACKENDS if tile == SQUARE_TILE else \
                ("rowpack", "plan")
            for d in densities:
                pruned, _ = prune_to_sparsity(w, tile, 1.0 - d)
                pk = pack_bsr(np.asarray(pruned), tile)
                # the dense baseline joins every group so each recorded
                # speedup_vs_dense is a *paired* measurement (machine drift
                # between groups cannot skew the ratio)
                arms = [("dense", dense, w)]
                arms += [(backend,) + _sparse_fn(pk, backend)
                         for backend in backends]
                # serving-density arms are fast: buy extra reps there so the
                # min-of-reps ordering is stable against scheduler noise
                # (the shared box needs ~30 paired reps to resolve <10% gaps)
                d_reps = reps if d > 0.2 or smoke else max(reps, 31)
                times, scores = _time_group([(fn, (x, data))
                                             for _, fn, data in arms],
                                            reps=d_reps)
                t_dense = times[0]
                for (backend, _, _), t_s in zip(arms, times):
                    emit(f"kernel/{name}_{backend}{tile_tag}"
                         f"_d{int(d*100):03d},{t_s*1e6:.1f},"
                         f"{t_dense/t_s:.3f}")
                    records.append({
                        "shape": name, "n": n, "k": k, "m": M,
                        "backend": backend, "tile": list(tile),
                        "density": d, "us": round(t_s * 1e6, 1),
                        "speedup_vs_dense": round(t_dense / t_s, 3)})
                # autotuner cross-check over this cell's candidate set:
                # its independent pick must land within 5% of the paired
                # measurement's best arm (stub mode: deterministic proxy).
                # Both sides use the same rep discipline AND the same
                # drift-robust ordering statistic (median paired ratio);
                # residual disagreement is then pure session-to-session
                # drift on genuine near-ties
                by_arm = {nm: t for (nm, _, _), t in zip(arms, times)}
                by_score = {nm: s for (nm, _, _), s in zip(arms, scores)}
                choice = choose_backend(
                    pk, m=M, candidates=tuple(by_arm), reps=d_reps)
                best = min(by_score, key=by_score.get)
                auto_records.append({
                    "shape": name, "tile": list(tile), "density": d,
                    "chosen": choice.backend, "best_measured": best,
                    "chosen_us": round(by_arm[choice.backend] * 1e6, 1),
                    "best_us": round(by_arm[best] * 1e6, 1),
                    "chosen_score": round(by_score[choice.backend], 4),
                    "best_score": round(by_score[best], 4),
                    "within_5pct": bool(by_score[choice.backend]
                                        <= 1.05 * by_score[best]),
                    "cache_hit": choice.cache_hit, "mode": choice.mode})
    n_ok = sum(r["within_5pct"] for r in auto_records)
    emit(f"# autotune: {n_ok}/{len(auto_records)} cells within 5% of best "
         f"fixed backend [{auto_records[0]['mode'] if auto_records else '-'}]")
    if write_json:
        # the smoke subset must not clobber the full sweep's trajectory
        section = "kernel_smoke" if smoke else "kernel"
        path = update_bench_json(section, records)
        update_bench_json("autotune_smoke" if smoke else "autotune",
                          auto_records)
        emit(f"# wrote {len(records)} records to {path} [{section}]")
    return records


def run_plan_bsr(emit=print, smoke=False, write_json=True, reps=7):
    """Plan-layout arms head to head: the XLA composition ('plan') vs the
    compiled plan-consuming Pallas kernel ('plan_pallas').

    Off-TPU the Pallas arm executes in interpret mode -- a correctness
    vehicle, not a serving path (docs/PERF.md) -- so it only runs in the
    smoke sweep at a tiny shape there; the recorded cells keep the two
    arms' trajectories comparable on TPU where both compile. Section
    schema matches the engine benches ({"results": {arm: [cells]}}) so
    scripts/bench_guard.py tracks it warn-only by ``rate`` (rows/s)."""
    rng = np.random.RandomState(0)
    on_tpu = jax.default_backend() == "tpu"
    if smoke:
        cells = [("proj_256", 256, 256, 64, 0.2)]
        reps = min(reps, 3)
    else:
        cells = [("proj_768", 768, 768, M, d) for d in (0.5, 0.2, 0.1)]
    results = {"plan": [], "plan_pallas": []}
    for name, n, k, m, d in cells:
        x = jnp.asarray(rng.randn(m, k).astype(np.float32))
        w = jnp.asarray(rng.randn(n, k).astype(np.float32))
        pruned, _ = prune_to_sparsity(w, SQUARE_TILE, 1.0 - d)
        pk = pack_bsr(np.asarray(pruned), SQUARE_TILE)
        plan = plan_for_pack(pk)
        data = pack_plan_data(plan, pk.data)
        arms = [("plan", jax.jit(
            lambda x_, d_, _p=plan: plan_linear(x_, d_, _p)))]
        if on_tpu or smoke:
            arms.append(("plan_pallas", jax.jit(
                lambda x_, d_, _p=plan: plan_linear_pallas(x_, d_, _p))))
        times, _ = _time_group([(fn, (x, data)) for _, fn in arms],
                               reps=reps)
        for (arm, _), t_s in zip(arms, times):
            cell = {"cell": f"{name}_d{int(d * 100):03d}", "density": d,
                    "m": m, "us": round(t_s * 1e6, 1),
                    "rate": round(m / t_s, 1)}
            results[arm].append(cell)
            emit(f"plan_bsr/{name}_{arm}_d{int(d * 100):03d},"
                 f"{t_s * 1e6:.1f},{m / t_s:.0f}")
    if write_json:
        section = "plan_bsr_smoke" if smoke else "plan_bsr"
        path = update_bench_json(section, {"results": results,
                                           "device": jax.default_backend()})
        emit(f"# wrote plan_bsr cells to {path} [{section}]")
    return results


def run_flash_decode(emit=print, smoke=False, write_json=True, reps=7):
    """Decode-attention arms over a context-length x split-K sweep: the
    materialized-softmax XLA path vs the split-K flash kernel
    (kernels/flash_decode.py). Off-TPU the flash arm is interpret-mode
    (smoke-only, tiny contexts); tokens_per_s = batch tokens emitted per
    decode step -- the metric bench_guard tracks warn-only."""
    rng = np.random.RandomState(0)
    on_tpu = jax.default_backend() == "tpu"
    b, hq, hkv, d = 8, 8, 4, 64
    if smoke:
        sweep = [(128, 1), (128, 2)]
        reps = min(reps, 3)
    else:
        sweep = [(t, s) for t in (256, 1024, 4096) for s in (1, 4, 8)
                 if s <= t // 128]
    results = {"xla": [], "flash": []}
    for t, split in sweep:
        q = jnp.asarray(rng.randn(b, 1, hq, d).astype(np.float32))
        k = jnp.asarray(rng.randn(b, t, hkv, d).astype(np.float32))
        v = jnp.asarray(rng.randn(b, t, hkv, d).astype(np.float32))
        kvp = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
        pos = jnp.full((b,), t - 1, jnp.int32)
        arms = [("xla", jax.jit(lambda *a: decode_attention(*a)))]
        if on_tpu or smoke:
            arms.append(("flash", jax.jit(
                lambda *a, _s=split: flash_decode(*a, kv_split=_s))))
        times, _ = _time_group([(fn, (q, k, v, kvp, pos)) for _, fn in arms],
                               reps=reps)
        for (arm, _), t_s in zip(arms, times):
            if arm == "xla" and split > 1:
                continue            # the XLA arm has no split axis
            results[arm].append({
                "cell": f"t{t}_s{split if arm == 'flash' else 1}",
                "context": t, "kv_split": split if arm == "flash" else 1,
                "us": round(t_s * 1e6, 1),
                "tokens_per_s": round(b / t_s, 1)})
            emit(f"flash_decode/{arm}_t{t}_s{split},{t_s * 1e6:.1f},"
                 f"{b / t_s:.0f}")
    if write_json:
        section = "flash_decode_smoke" if smoke else "flash_decode"
        path = update_bench_json(section, {"results": results,
                                           "device": jax.default_backend()})
        emit(f"# wrote flash_decode cells to {path} [{section}]")
    return results


if __name__ == "__main__":
    use_compile_cache()
    smoke = "--smoke" in sys.argv
    write_json = "--no-json" not in sys.argv
    run(smoke=smoke, write_json=write_json)
    run_plan_bsr(smoke=smoke, write_json=write_json)
    run_flash_decode(smoke=smoke, write_json=write_json)
