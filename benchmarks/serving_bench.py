"""Serving-engine throughput benchmark: tokens/s vs request concurrency.

Runs the continuous-batching engine (repro/serving/engine.py) over a
BERT-sized decoder-only LM (12L x 768d, the paper's model size moved into
the decode regime) at 1 / 4 / 16 request slots, sparse (80% block-pruned,
plan backend) against dense (same weights, no BSR support -- the paper's
negative control). Each cell submits 2x slots requests of mixed prompt
lengths, so admission, bucketed prefill, slot recycling and the batched
ragged decode all exercise on the hot path.

Two sections are produced:

  * "engine" -- the per-step loop (sync_every=1): one host round-trip per
    token, the PR-3 baseline. Each cell now also reports the wall-clock
    breakdown (prefill vs decode vs host-sync seconds, engine stats).
  * "engine_fused" -- the fused-window loop: a ``--sync-every`` sweep at
    the highest concurrency, where K decode steps run inside one jitted
    scan and the host syncs once per window (models.api.decode_many).
    The per-step baseline showed ~200 ms/step on this box with the device
    busy for a fraction of it -- host dispatch, the overhead regime the
    CPU sparse-serving literature says to engineer away (arXiv:2306.16601).

An "engine_chaos" section measures the request-lifecycle robustness
layer's overhead: the same fused workload through a bare engine vs one
with deadlines, a bounded queue, a watchdog and a chaos registry armed --
lifecycle enforcement happens at window-sync points only, so the two arms
should match to noise (docs/PERF.md §Engine robustness overhead).

A "sharded" section sweeps the mesh path (``--mesh 1,2,8``): the
same engine workload served tensor-parallel over a ``(1, S)`` device mesh
(spec ``mesh_shape``), reporting tok/s plus per-device pack and cache
bytes -- the partitioning evidence. Mesh sizes the process cannot host
(fewer visible devices) are skipped with a note; on CPU run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``. Host-platform
"devices" share one socket, so the sharded tok/s measure partitioning
OVERHEAD, not interconnect speedups (docs/PERF.md).

Results are persisted to BENCH_serving.json at the repo root via
repro.runtime.bench_io, keeping the perf trajectory machine-readable
across PRs; scripts/check.sh warns when a fresh smoke regresses >20%
against the committed numbers (scripts/bench_guard.py).

An "open_loop" section (``run_open_loop``) measures SLO latency under
seeded Poisson arrivals at fixed offered QPS: the full SLO scheduler
(chunked prefill + token budget + decode priority + queue-delay
shedding, SchedSpec) against the serve-everyone monolithic-prefill
baseline on the same arrival trace, reporting p50/p95/p99 TTFT and
per-token latency over completed requests plus shed counts
(docs/PERF.md §Open-loop serving).

Run:  PYTHONPATH=src python benchmarks/serving_bench.py
          [--smoke] [--no-json] [--skip-baseline] [--sync-every 1,4,8,16]
          [--mesh 1,2,8] [--qps 4,8,16] [run_* selector ...]
"""
from __future__ import annotations

import os
import sys
import time

import jax
import numpy as np

from repro.configs.base import LayerKind, ModelConfig
from repro.models import init_model
from repro.runtime.bench_io import repo_root, update_bench_json
from repro.runtime.compile_cache import use_compile_cache
from repro.serving import ServingSpec, prepare_servable

SLOT_COUNTS = (1, 4, 16)
SPARSITY = 0.8
TILE = (64, 64)
SYNC_SWEEP = (1, 4, 8, 16)
SYNC_SWEEP_SMOKE = (1, 4)
MESH_SWEEP = (1, 2, 8)


def bench_path() -> str:
    return os.path.join(repo_root(), "BENCH_serving.json")


def _bert_sized_lm(smoke: bool) -> ModelConfig:
    if smoke:
        return ModelConfig(
            arch="serving-bench-smoke", family="dense",
            n_layers=4, d_model=256, n_heads=4, n_kv_heads=4, head_dim=64,
            d_ff=1024, vocab_size=4096,
            pattern=(LayerKind("attn", "dense"),), dtype="float32")
    return ModelConfig(
        arch="serving-bench-bert-lm", family="dense",
        n_layers=12, d_model=768, n_heads=12, n_kv_heads=12, head_dim=64,
        d_ff=3072, vocab_size=30522,
        pattern=(LayerKind("attn", "dense"),), dtype="float32")


def _run_cell(servable, slots, *, prompt_len, max_new, cache_len, rng,
              reps=2, sync_every=1, engine_kw=None, submit_kw=None):
    """One (backend, concurrency, sync_every) cell: warm the jit caches
    with a single-request run at the same window length (so every fused-K
    executable the timed run needs is already traced), then time a
    2x-slots request burst ``reps`` times and keep the fastest (scheduler
    noise on the shared box is one-sided -- it only slows a run down -- so
    min-of-reps approximates the quiet-machine time, same discipline as
    kernel_bench). ``engine_kw`` / ``submit_kw`` forward robustness knobs
    (deadlines, bounded queue, watchdog, chaos) for the engine_chaos
    section."""
    engine_kw = engine_kw or {}
    submit_kw = submit_kw or {}
    warm = servable.engine(max_slots=slots, cache_len=cache_len,
                           sync_every=sync_every, **engine_kw)
    warm.submit(rng.randint(0, servable.cfg.vocab_size, (prompt_len,)),
                max_new_tokens=max_new, **submit_kw)
    warm.run()
    warm.close()

    best = None
    for _ in range(reps):
        eng = servable.engine(max_slots=slots, cache_len=cache_len,
                              sync_every=sync_every, **engine_kw)
        # same bucket as the warmup (prompt lengths vary under one power of
        # two) so the timed runs pay zero compilation
        lens = [max(2, prompt_len - (i % 4)) for i in range(2 * slots)]
        reqs = [eng.submit(rng.randint(0, servable.cfg.vocab_size, (L,)),
                           max_new_tokens=max_new, **submit_kw)
                for L in lens]
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        eng.close()
        assert all(r.done for r in reqs)
        if best is None or dt < best[0]:
            best = (dt, eng, len(reqs))
    dt, eng, n_reqs = best
    toks = eng.stats.tokens_generated
    st = eng.stats
    cell = {"slots": slots, "requests": n_reqs, "tokens": toks,
            "seconds": round(dt, 4), "tokens_per_s": round(toks / dt, 2),
            "sync_every": sync_every,
            "decode_steps": st.steps, "windows": st.windows,
            "mean_occupancy": round(st.mean_occupancy, 2),
            "prefill_buckets": dict(st.bucket_hits),
            # wall-clock breakdown (seconds, engine-measured): prompt
            # prefill, decode windows (device call -> outputs on host),
            # host-side sync (token drain + callbacks + slot recycling)
            "breakdown": {
                "prefill_s": round(st.prefill_s, 4),
                "decode_s": round(st.decode_s, 4),
                "sync_s": round(st.sync_s, 4),
                "decode_ms_per_step": round(
                    1e3 * st.decode_s / max(st.steps, 1), 2),
                "sync_ms_per_window": round(
                    1e3 * st.sync_s / max(st.windows, 1), 2),
            }}
    # the timed engine rides along so callers can read post-run state
    # (e.g. run_sharded's per-device cache bytes) without building another
    return eng, cell


def _bench_params(smoke: bool):
    return {"prompt_len": 8 if smoke else 16,
            "max_new": 8 if smoke else 32,
            "cache_len": 64 if smoke else 128}


#: the paper's targets: attention AND the FC projections, where most of
#: the decode FLOPs live (ffn export for lm families landed with the
#: fused-decode PR; both arms prune identically -- dense is the
#: no-format-support negative control over the SAME pruned weights)
TARGETS = ("attn/wq", "attn/wk", "attn/wv", "attn/wo",
           "ffn/wi", "ffn/wg", "ffn/wo")


def _build_arms(cfg, emit):
    emit(f"initializing {cfg.arch} ({cfg.n_layers}L x {cfg.d_model}d)...")
    params = init_model(jax.random.PRNGKey(0), cfg)
    # tied masks: one pattern shared by all layers of a scan-stacked group,
    # so the group's union pack stays at the target density (independent
    # per-layer masks would union to ~1 - (1-d)^L tile density)
    arms = {
        "sparse": prepare_servable(params, cfg, ServingSpec(
            tile=TILE, sparsity=SPARSITY, prune="tied", targets=TARGETS,
            backend="plan")),
        "dense": prepare_servable(params, cfg, ServingSpec(
            tile=TILE, sparsity=SPARSITY, prune="tied", targets=TARGETS,
            backend="dense")),
    }
    emit(f"sparse export: density="
         f"{arms['sparse'].stats()['density']:.2f} (target {SPARSITY:.0%} "
         f"pruned @ {TILE[0]}x{TILE[1]})")
    return arms


def run(emit=print, smoke=False, write_json=True, arms=None):
    cfg = _bert_sized_lm(smoke)
    bp = _bench_params(smoke)
    prompt_len, max_new, cache_len = (bp["prompt_len"], bp["max_new"],
                                      bp["cache_len"])
    rng = np.random.RandomState(0)
    arms = arms or _build_arms(cfg, emit)

    results = {name: [] for name in arms}
    emit(f"{'arm':8s} {'slots':>5s} {'tokens':>7s} {'sec':>8s} "
         f"{'tok/s':>8s} {'occupancy':>9s}")
    for slots in SLOT_COUNTS:
        for name, servable in arms.items():
            _, cell = _run_cell(servable, slots, prompt_len=prompt_len,
                                max_new=max_new, cache_len=cache_len,
                                rng=rng, reps=1 if smoke else 2)
            results[name].append(cell)
            emit(f"{name:8s} {cell['slots']:5d} {cell['tokens']:7d} "
                 f"{cell['seconds']:8.3f} {cell['tokens_per_s']:8.1f} "
                 f"{cell['mean_occupancy']:9.2f}")

    scaling = {name: round(cells[-1]["tokens_per_s"] /
                           cells[0]["tokens_per_s"], 2)
               for name, cells in results.items()}
    emit(f"throughput scaling {SLOT_COUNTS[0]} -> {SLOT_COUNTS[-1]} slots: "
         + ", ".join(f"{k} {v}x" for k, v in scaling.items()))

    if write_json:
        section = "engine_smoke" if smoke else "engine"
        path = update_bench_json(section, {
            "model": cfg.arch,
            "layers": cfg.n_layers, "d_model": cfg.d_model,
            "sparsity": SPARSITY, "tile": list(TILE),
            "prompt_len": prompt_len, "max_new_tokens": max_new,
            "slot_counts": list(SLOT_COUNTS),
            "results": results,
            "throughput_scaling": scaling,
        }, path=bench_path())
        emit(f"wrote {section} section to {path}")
    return results


def run_fused(emit=print, smoke=False, write_json=True, sync_sweep=None,
              arms=None):
    """The tentpole measurement: tokens/s vs ``sync_every`` at the highest
    concurrency, fused windows against the per-step loop (sync_every=1 in
    the same sweep doubles as the paired baseline). Reports the wall-clock
    breakdown per cell so the host-dispatch share is visible."""
    cfg = _bert_sized_lm(smoke)
    bp = _bench_params(smoke)
    slots = 4 if smoke else SLOT_COUNTS[-1]
    sweep = tuple(sync_sweep or (SYNC_SWEEP_SMOKE if smoke else SYNC_SWEEP))
    rng = np.random.RandomState(1)
    arms = arms or _build_arms(cfg, emit)

    results = {name: [] for name in arms}
    emit(f"{'arm':8s} {'sync':>5s} {'tokens':>7s} {'sec':>8s} "
         f"{'tok/s':>8s} {'dec ms/step':>12s}")
    for sync_every in sweep:
        for name, servable in arms.items():
            _, cell = _run_cell(servable, slots, rng=rng,
                                prompt_len=bp["prompt_len"],
                                max_new=bp["max_new"],
                                cache_len=bp["cache_len"],
                                reps=1 if smoke else 2,
                                sync_every=sync_every)
            results[name].append(cell)
            emit(f"{name:8s} {sync_every:5d} {cell['tokens']:7d} "
                 f"{cell['seconds']:8.3f} {cell['tokens_per_s']:8.1f} "
                 f"{cell['breakdown']['decode_ms_per_step']:12.2f}")

    # "vs per-step" only means something when the sweep actually contains
    # the per-step arm (sync_every == 1); a custom sweep without it gets
    # no speedup record rather than a mislabeled ratio in the trajectory
    def _speedup(cells):
        base = [c for c in cells if c["sync_every"] == 1]
        if not base:
            return None
        return round(max(c["tokens_per_s"] for c in cells) /
                     base[0]["tokens_per_s"], 2)
    speedup = {name: _speedup(cells) for name, cells in results.items()}
    if all(v is not None for v in speedup.values()):
        emit("fused speedup vs per-step (best cell / sync-1 cell): "
             + ", ".join(f"{k} {v}x" for k, v in speedup.items()))
    else:
        emit("(no sync_every=1 arm in sweep: per-step speedup not recorded)")

    if write_json:
        section = "engine_fused_smoke" if smoke else "engine_fused"
        path = update_bench_json(section, {
            "model": cfg.arch,
            "layers": cfg.n_layers, "d_model": cfg.d_model,
            "sparsity": SPARSITY, "tile": list(TILE),
            "prompt_len": bp["prompt_len"], "max_new_tokens": bp["max_new"],
            "slots": slots, "sync_every_sweep": list(sweep),
            "results": results,
            "fused_speedup_vs_per_step": speedup,
        }, path=bench_path())
        emit(f"wrote {section} section to {path}")
    return results


def _tp_lm(smoke: bool) -> ModelConfig:
    """A decoder whose projections divide an 8-wide model axis at the
    sharded tile (wqkv block rows, ffn rows/cols, kv heads all % 8 == 0)."""
    if smoke:
        return ModelConfig(
            arch="serving-bench-tp-smoke", family="dense",
            n_layers=4, d_model=256, n_heads=8, n_kv_heads=8, head_dim=32,
            d_ff=1024, vocab_size=4096,
            pattern=(LayerKind("attn", "dense"),), dtype="float32")
    return ModelConfig(
        arch="serving-bench-tp", family="dense",
        n_layers=12, d_model=512, n_heads=8, n_kv_heads=8, head_dim=64,
        d_ff=2048, vocab_size=30522,
        pattern=(LayerKind("attn", "dense"),), dtype="float32")


def _per_device_bytes(tree) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return int(sum(
        np.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
        if hasattr(x, "sharding") else x.nbytes for x in leaves))


def run_sharded(emit=print, smoke=False, write_json=True, mesh_sweep=None):
    """The mesh sweep: the fused-engine workload served over (1, S) meshes.
    Emits tok/s + per-device pack/cache bytes per mesh size -- the
    evidence that TP export actually partitions state. Host-platform
    meshes measure partitioning overhead, not interconnects."""
    cfg = _tp_lm(smoke)
    bp = _bench_params(smoke)
    tile = (32, 32) if smoke else (64, 64)
    slots = 4 if smoke else 8
    sweep = tuple(mesh_sweep or MESH_SWEEP)
    rng = np.random.RandomState(2)

    emit(f"initializing {cfg.arch} ({cfg.n_layers}L x {cfg.d_model}d), "
         f"mesh sweep {sweep} ({jax.device_count()} devices visible)...")
    params = init_model(jax.random.PRNGKey(0), cfg)
    results = {}
    skipped = []
    emit(f"{'mesh':>6s} {'tokens':>7s} {'sec':>8s} {'tok/s':>8s} "
         f"{'pack/dev':>10s} {'cache/dev':>10s}")
    for s in sweep:
        if s > jax.device_count():
            skipped.append(s)
            continue
        spec = ServingSpec(
            tile=tile, sparsity=SPARSITY, prune="tied", targets=TARGETS,
            backend="plan",
            mesh_shape=(1, s) if s > 1 else None, partition="tp")
        servable = prepare_servable(params, cfg, spec)
        eng, cell = _run_cell(servable, slots, prompt_len=bp["prompt_len"],
                              max_new=bp["max_new"],
                              cache_len=bp["cache_len"],
                              rng=rng, reps=1 if smoke else 2, sync_every=4)
        _, cell["pack_bytes_per_device"] = servable.pack_bytes()
        cell["cache_bytes_per_device"] = _per_device_bytes(eng.cache)
        st = servable.stats()
        if "sharding" in st:
            cell["sharded_packs"] = st["sharding"]["sharded_packs"]
        results[f"tp{s}"] = [cell]
        emit(f"{'tp' + str(s):>6s} {cell['tokens']:7d} "
             f"{cell['seconds']:8.3f} {cell['tokens_per_s']:8.1f} "
             f"{cell['pack_bytes_per_device']:10d} "
             f"{cell['cache_bytes_per_device']:10d}")
    for s in skipped:
        emit(f"(mesh tp{s} skipped: needs {s} devices, "
             f"{jax.device_count()} visible -- set XLA_FLAGS="
             f"--xla_force_host_platform_device_count={max(sweep)})")

    if write_json and results:
        section = "sharded_smoke" if smoke else "sharded"
        path = update_bench_json(section, {
            "model": cfg.arch, "layers": cfg.n_layers,
            "d_model": cfg.d_model, "sparsity": SPARSITY,
            "tile": list(tile), "slots": slots,
            "mesh_sweep": list(sweep), "skipped": skipped,
            "devices_visible": jax.device_count(),
            "results": results,
        }, path=bench_path())
        emit(f"wrote {section} section to {path}")
    return results


def run_chaos(emit=print, smoke=False, write_json=True, arms=None):
    """The lifecycle-overhead cell: the fused-engine workload served twice
    over the SAME sparse servable -- once through a bare engine
    ("baseline") and once with the whole robustness layer armed
    ("lifecycle": bounded queue, per-request deadlines + priorities, a
    watchdog thread, and an attached-but-unarmed chaos registry). The
    deadline/cancel sweep and queue accounting run at window-sync points
    only, so the two arms should measure the same tok/s to noise
    (docs/PERF.md); bench_guard tracks the cell warn-only so a future PR
    that accidentally puts lifecycle checks on the per-token path shows up
    in the trajectory."""
    from repro.runtime.chaos import ChaosInjector
    cfg = _bert_sized_lm(smoke)
    bp = _bench_params(smoke)
    slots = 4 if smoke else SLOT_COUNTS[-1]
    sync_every = 4
    rng = np.random.RandomState(3)
    arms = arms or _build_arms(cfg, emit)
    servable = arms["sparse"]

    cells = {
        "baseline": ({}, {}),
        "lifecycle": ({"max_queue": 4 * slots, "overflow": "reject",
                       "watchdog_timeout_s": 60.0,
                       "chaos": ChaosInjector()},
                      {"deadline_s": 600.0, "priority": 1}),
    }
    results = {}
    emit(f"{'arm':10s} {'tokens':>7s} {'sec':>8s} {'tok/s':>8s}")
    for name, (engine_kw, submit_kw) in cells.items():
        _, cell = _run_cell(servable, slots, prompt_len=bp["prompt_len"],
                            max_new=bp["max_new"],
                            cache_len=bp["cache_len"], rng=rng,
                            reps=1 if smoke else 2, sync_every=sync_every,
                            engine_kw=engine_kw, submit_kw=submit_kw)
        results[name] = [cell]
        emit(f"{name:10s} {cell['tokens']:7d} {cell['seconds']:8.3f} "
             f"{cell['tokens_per_s']:8.1f}")
    overhead = round(
        results["baseline"][0]["tokens_per_s"] /
        results["lifecycle"][0]["tokens_per_s"] - 1.0, 4)
    emit(f"lifecycle overhead vs baseline: {overhead:+.2%} "
         f"(sync-point enforcement: expected ~0)")

    if write_json:
        section = "engine_chaos_smoke" if smoke else "engine_chaos"
        path = update_bench_json(section, {
            "model": cfg.arch, "layers": cfg.n_layers,
            "d_model": cfg.d_model, "sparsity": SPARSITY,
            "tile": list(TILE), "slots": slots, "sync_every": sync_every,
            "prompt_len": bp["prompt_len"], "max_new_tokens": bp["max_new"],
            "results": results,
            "lifecycle_overhead": overhead,
        }, path=bench_path())
        emit(f"wrote {section} section to {path}")
    return results


def run_kv_memory(emit=print, smoke=False, write_json=True, arms=None):
    """The paged-KV memory cells (docs/API.md §Paged KV + prefix cache):

      * per-request KV bytes -- dense reserves a full ``cache_len`` slot
        per request; paged reserves ``ceil((len + max_new) / page_size)``
        pages, so short requests stop paying for the worst case.
      * max concurrent requests at a FIXED KV byte budget -- the dense
        engine's whole cache allocation is taken as the budget, a paged
        pool of exactly that many bytes serves a mixed-length burst, and
        the peak concurrently-active count is measured (not derived).
      * shared-system-prompt workload -- every request repeats one system
        prompt; the radix prefix cache turns the repeats into page reuse.
        Reports the prefix-hit rate and the measured mean/p50 TTFT against
        the dense arm (same requests, full prefill each).

    All cells ride the same servable; the engine's ``kv_layout`` kwarg
    picks the layout so both arms share weights, packs and jit caches."""
    cfg = _bert_sized_lm(smoke)
    bp = _bench_params(smoke)
    cache_len, max_new = bp["cache_len"], bp["max_new"]
    slots = 4 if smoke else 8
    rng = np.random.RandomState(4)
    arms = arms or _build_arms(cfg, emit)
    servable = arms["sparse"]
    V = cfg.vocab_size

    def fresh(layout, **kw):
        return servable.engine(max_slots=slots, cache_len=cache_len,
                               sync_every=4, kv_layout=layout, **kw)

    # -- cell 1: per-request KV bytes -----------------------------------
    eng_d = fresh("dense")
    eng_p = fresh("paged")
    kv_d, kv_p = eng_d.kv_stats(), eng_p.kv_stats()
    ps = kv_p["page_size"]
    from repro.serving.paging import pages_needed
    mixed_lens = [max(2, int(L)) for L in
                  np.linspace(4, cache_len - max_new, 8)]
    per_req_paged = [pages_needed(L + max_new, ps) * kv_p["bytes_per_page"]
                     for L in mixed_lens]
    bytes_cell = {
        "dense_bytes_per_request": kv_d["kv_bytes_per_slot"],
        "paged_bytes_per_request_mixed": per_req_paged,
        "paged_mean_bytes_per_request": int(np.mean(per_req_paged)),
        "page_size": ps, "bytes_per_page": kv_p["bytes_per_page"],
        "mixed_prompt_lens": mixed_lens, "max_new_tokens": max_new,
    }
    emit(f"KV bytes/request: dense {kv_d['kv_bytes_per_slot']}, paged "
         f"mean {bytes_cell['paged_mean_bytes_per_request']} over mixed "
         f"lens {mixed_lens[0]}..{mixed_lens[-1]}")
    eng_d.close(), eng_p.close()

    # -- cell 2: max concurrency at the dense engine's byte budget -------
    budget = kv_d["kv_bytes_total"]
    pool_pages = max(1, budget // kv_p["bytes_per_page"])
    eng = servable.engine(max_slots=4 * slots, cache_len=cache_len,
                          sync_every=4, kv_layout="paged",
                          kv_pool_pages=pool_pages, max_queue=None)
    burst, peak = [], 0
    for i in range(4 * slots):
        L = mixed_lens[i % len(mixed_lens)]
        burst.append(eng.submit(rng.randint(0, V, (L,)),
                                max_new_tokens=max_new))
    while eng.step():
        peak = max(peak, eng.n_active)
    assert all(r.done for r in burst)
    concurrency_cell = {
        "kv_byte_budget": budget, "pool_pages": pool_pages,
        "dense_max_concurrent": slots,      # budget / full-slot bytes
        "paged_peak_concurrent": peak,
        "paged_peak_pages_used": eng.kv_stats()["peak_pages_used"],
    }
    emit(f"max concurrent @ {budget} KV bytes: dense {slots}, "
         f"paged {peak} (peak pages {concurrency_cell['paged_peak_pages_used']}"
         f"/{pool_pages})")
    eng.close()

    # -- cell 3: shared-system-prompt workload ---------------------------
    # exactly `slots` requests: all admit in the first schedule pass, so
    # TTFT measures admission (prefill) latency, not queue wait behind
    # decode throughput -- the decode tax shows in tokens_per_s instead
    system = rng.randint(0, V, (cache_len // 2,)).tolist()
    tails = [rng.randint(0, V, (3,)).tolist() for _ in range(slots)]
    results = {}
    for name in ("dense", "paged"):
        # warm the jit caches off-clock: two shared-prefix requests so the
        # paged arm compiles BOTH admission paths (full prefill + insert,
        # then match + restore + suffix prefill at the tail bucket)
        warm = fresh(name)
        warm.submit(system + tails[0], max_new_tokens=max_new)
        warm.submit(system + tails[1], max_new_tokens=max_new)
        warm.run()
        warm.close()
        eng = fresh(name, max_queue=None)
        first_tok = {}
        t0 = time.perf_counter()
        reqs = [eng.submit(system + tail, max_new_tokens=max_new,
                           on_token=lambda rid, tok: first_tok.setdefault(
                               rid, time.perf_counter() - t0))
                for tail in tails]
        eng.run()
        dt = time.perf_counter() - t0
        assert all(r.done for r in reqs)
        st = eng.stats
        kv = eng.kv_stats()
        ttfts = sorted(first_tok[r.req_id] for r in reqs)
        prompt_tokens = sum(len(system) + len(t) for t in tails)
        results[name] = [{
            "slots": slots, "requests": len(reqs), "sync_every": 4,
            "tokens": st.tokens_generated, "seconds": round(dt, 4),
            "tokens_per_s": round(st.tokens_generated / dt, 2),
            "prompt_tokens": prompt_tokens,
            "prefilled_tokens": kv["prefilled_tokens"],
            "prefix_hit_tokens": kv["prefix_hit_tokens"],
            "prefix_hit_rate": round(
                kv["prefix_hit_tokens"] / prompt_tokens, 4),
            "prefill_s": round(st.prefill_s, 4),
            # the paged decode tax (per-step page gather) lives here
            "decode_ms_per_step": round(
                1e3 * st.decode_s / max(st.steps, 1), 2),
            "ttft_mean_ms": round(1e3 * float(np.mean(ttfts)), 2),
            "ttft_p50_ms": round(1e3 * ttfts[len(ttfts) // 2], 2),
        }]
        c = results[name][0]
        emit(f"{name:8s} shared-prompt: hit rate {c['prefix_hit_rate']:.0%} "
             f"ttft mean {c['ttft_mean_ms']:.1f} ms  "
             f"prefilled {c['prefilled_tokens']}/{prompt_tokens} tok  "
             f"{c['tokens_per_s']:.1f} tok/s")
    ttft_reduction = round(
        1.0 - results["paged"][0]["ttft_mean_ms"] /
        results["dense"][0]["ttft_mean_ms"], 4)
    emit(f"prefix sharing TTFT reduction vs dense: {ttft_reduction:+.2%}")

    if write_json:
        section = "kv_memory_smoke" if smoke else "kv_memory"
        path = update_bench_json(section, {
            "model": cfg.arch, "layers": cfg.n_layers,
            "d_model": cfg.d_model, "sparsity": SPARSITY,
            "tile": list(TILE), "cache_len": cache_len,
            "max_new_tokens": max_new,
            "bytes_per_request": bytes_cell,
            "fixed_budget_concurrency": concurrency_cell,
            "results": results,
            "ttft_reduction_vs_dense": ttft_reduction,
        }, path=bench_path())
        emit(f"wrote {section} section to {path}")
    return results


def _latency_pcts(xs_s):
    """p50/p95/p99 of a latency sample, reported in milliseconds."""
    if not xs_s:
        return {"p50_ms": None, "p95_ms": None, "p99_ms": None}
    a = np.asarray(sorted(xs_s), dtype=np.float64)
    return {f"p{q}_ms": round(1e3 * float(np.percentile(a, q)), 2)
            for q in (50, 95, 99)}


def run_open_loop(emit=print, smoke=False, write_json=True, arms=None,
                  qps_sweep=None):
    """Open-loop SLO measurement (docs/PERF.md §Open-loop serving): seeded
    Poisson arrivals at a fixed offered QPS over a mixed-length workload
    (rare near-cache-sized prompts inside interactive traffic), submitted
    on their own clock -- arrivals do NOT wait for the engine, so queueing
    delay is measured rather than hidden (closed-loop benches self-throttle
    to the engine's pace and can't see head-of-line blocking at all).

    Two arms share one servable and the identical arrival/length trace:

      * "baseline" -- the PR-8 engine (monolithic prefill, FIFO+priority
        admission, serve-everyone): a long prompt's prefill occupies the
        whole scheduling pass, and under overload the queue grows without
        bound -- every request is eventually served, arbitrarily late.
      * "sched"    -- the full SLO feature set: SchedSpec(max_chunk,
        token_budget, decode_priority, max_queue_delay_s). Long prefills
        are sliced across windows with new arrivals admitted BETWEEN
        slices, running decodes keep a reserved token share, and when the
        estimated backlog drain time exceeds the queue-delay SLO the
        engine SHEDS (lowest-priority, newest-first) instead of serving
        everyone late.

    Reports TTFT (first token relative to the request's OFFERED arrival
    time, queue wait included) and per-token decode latency (TPOT) as
    p50/p95/p99 per offered QPS over the COMPLETED requests, with shed
    counts alongside -- the goodput framing: under overload an SLO-aware
    engine refuses work it cannot serve in time, so its percentiles cover
    fewer, faster requests BY DESIGN (the shed column is the other half
    of that trade; at stable load nothing sheds and the populations are
    identical). The p95-TTFT delta between arms is the SLO evidence the
    acceptance gate reads. Host-platform numbers characterize SCHEDULER
    behavior (relative arm-to-arm deltas), not hardware serving
    latency."""
    from repro.serving import SchedSpec
    cfg = _bert_sized_lm(smoke)
    slots = 8
    sync_every = 4
    cache_len = 512
    max_new = 8
    # interactive traffic with an occasional huge prompt: every 25th
    # request carries a near-cache-sized prompt (the tail-latency story is
    # the MANY shorts being protected from the RARE long, so the long
    # fraction is kept low enough that overall p95 reads the short
    # population; per-class percentiles are reported either way). The
    # smoke sweep sits in the moderate-to-deep overload regime where SLO
    # scheduling has something to do -- at stable load (the full sweep's
    # 8 qps cell) the arms are at parity by design.
    short_len, long_len, long_every = 8, 448, 25
    n_requests = 60 if smoke else 120
    sweep = tuple(qps_sweep or ((24.0, 40.0) if smoke
                                else (8.0, 24.0, 40.0)))
    sched = SchedSpec(max_chunk=128, token_budget=256, decode_priority=True,
                      max_queue_delay_s=0.25)
    arms = arms or _build_arms(cfg, emit)
    servable = arms["sparse"]
    V = cfg.vocab_size

    def fresh(use_sched):
        return servable.engine(max_slots=slots, cache_len=cache_len,
                               sync_every=sync_every, max_queue=None,
                               sched=sched if use_sched else None)

    # warm both arms' jit caches off-clock: one long + one short prompt
    # covers the monolithic buckets (128, 8) and the chunk buckets (16, 8)
    wrng = np.random.RandomState(9)
    for use_sched in (False, True):
        warm = fresh(use_sched)
        warm.submit(wrng.randint(0, V, (long_len,)), max_new_tokens=max_new)
        warm.submit(wrng.randint(0, V, (short_len,)), max_new_tokens=max_new)
        warm.run()
        warm.close()

    results = {"baseline": [], "sched": []}
    improvement = {}
    emit(f"{'arm':9s} {'qps':>5s} {'done':>5s} {'shed':>5s} "
         f"{'ttft p50':>9s} {'ttft p95':>9s} {'tpot p95':>9s} "
         f"{'tok/s':>7s}")
    for qps in sweep:
        # one seeded trace per QPS, replayed identically by both arms
        trace_rng = np.random.RandomState(int(qps * 1000) + 17)
        arrivals = np.cumsum(trace_rng.exponential(1.0 / qps, n_requests))
        lens = [long_len if (i + 1) % long_every == 0 else short_len
                for i in range(n_requests)]
        prompts = [trace_rng.randint(0, V, (int(L),)) for L in lens]
        for arm in ("baseline", "sched"):
            eng = fresh(arm == "sched")
            reqs = []
            t0 = time.monotonic()
            i = 0
            while i < n_requests:
                now = time.monotonic() - t0
                if arrivals[i] <= now:
                    reqs.append(eng.submit(prompts[i],
                                           max_new_tokens=max_new))
                    i += 1
                    continue
                if not eng.step():      # idle: sleep until the next arrival
                    time.sleep(min(arrivals[i] - now, 0.02))
            eng.run()                   # drain the tail
            assert all(r.finished for r in reqs)
            # latency percentiles cover COMPLETED requests (goodput);
            # shed/deadline counts in the cell are the other half
            served = [(r, arr, L) for r, arr, L
                      in zip(reqs, arrivals, lens) if r.status == "done"]
            ttfts = [r.first_token_at - (t0 + arr) for r, arr, _ in served]
            tpots = [(r.finished_at - r.first_token_at) /
                     (len(r.tokens) - 1)
                     for r, _, _ in served if len(r.tokens) > 1]
            st = eng.stats
            wall = max(r.finished_at for r, _, _ in served) - t0
            cell = {"arm": arm, "qps": qps, "requests": n_requests,
                    "completed": st.completed, "shed": st.shed,
                    "deadline_misses": st.deadline_misses,
                    "prefill_chunks": st.prefill_chunks,
                    "tokens_per_s": round(st.tokens_generated / wall, 2),
                    "ttft": _latency_pcts(ttfts),
                    "ttft_short": _latency_pcts(
                        [t for t, (_, _, L) in zip(ttfts, served)
                         if L == short_len]),
                    "ttft_long": _latency_pcts(
                        [t for t, (_, _, L) in zip(ttfts, served)
                         if L == long_len]),
                    "tpot": _latency_pcts(tpots)}
            results[arm].append(cell)
            emit(f"{arm:9s} {qps:5.1f} {cell['completed']:5d} "
                 f"{cell['shed']:5d} "
                 f"{cell['ttft']['p50_ms']:9.1f} "
                 f"{cell['ttft']['p95_ms']:9.1f} "
                 f"{cell['tpot']['p95_ms']:9.1f} "
                 f"{cell['tokens_per_s']:7.1f}")
            eng.close()
        base_p95 = results["baseline"][-1]["ttft"]["p95_ms"]
        sched_p95 = results["sched"][-1]["ttft"]["p95_ms"]
        improvement[str(qps)] = round(base_p95 - sched_p95, 2)
        emit(f"  p95 TTFT delta @ {qps} qps: "
             f"{improvement[str(qps)]:+.1f} ms (positive = sched wins)")

    if write_json:
        section = "open_loop_smoke" if smoke else "open_loop"
        path = update_bench_json(section, {
            "model": cfg.arch, "layers": cfg.n_layers,
            "d_model": cfg.d_model, "sparsity": SPARSITY,
            "tile": list(TILE), "slots": slots, "sync_every": sync_every,
            "cache_len": cache_len, "max_new_tokens": max_new,
            "short_len": short_len, "long_len": long_len,
            "long_every": long_every, "requests_per_cell": n_requests,
            "qps_sweep": list(sweep),
            "sched": {"max_chunk": sched.max_chunk,
                      "token_budget": sched.token_budget,
                      "decode_priority": sched.decode_priority,
                      "max_queue_delay_s": sched.max_queue_delay_s},
            "results": results,
            "p95_ttft_improvement_ms": improvement,
        }, path=bench_path())
        emit(f"wrote {section} section to {path}")
    return results


def run_quant_error(emit=print, smoke=False, write_json=True, arms=None):
    """The quantized-pack cells (docs/API.md §Quantized sparse packs): the
    fp32 plan arm against the SAME pruned weights exported with
    ``pack_quant='int8'`` (per-block absmax scales, dequant fused into the
    plan matmul). Three numbers matter:

      * pack bytes -- fp32 vs int8+scales, total and per device; the
        acceptance gate wants >= 3x smaller (int8 is 4x on values, the
        scale stream gives a little back).
      * fidelity -- max abs logit delta, teacher-forced next-token
        agreement (identical context per position, the standard metric)
        and free-running engine greedy agreement on identical prompts
        (both arms at temperature 0, same seeds), alongside the model's
        own top-2 logit margins. The >= 99% gate holds on the
        config-registry models (tests/test_quant_packs.py, gemma3);
        THIS model is random-init, so its margins sit at the quant
        noise floor and the agreement here reads against
        `logit_margins` (docs/PERF.md §Quantized packs). bench_guard
        warns if agreement or the delta drifts.
      * throughput -- tok/s per arm through the fused engine loop, so the
        dequant-fused path's cost (or win) is on the record next to the
        bytes it saves.
    """
    cfg = _bert_sized_lm(smoke)
    bp = _bench_params(smoke)
    slots = 4 if smoke else SLOT_COUNTS[-1]
    sync_every = 4
    rng = np.random.RandomState(5)
    arms = arms or _build_arms(cfg, emit)
    fp32 = arms["sparse"]
    emit("exporting int8 arm (same pruned weights, pack_quant='int8')...")
    # init_model is deterministic: PRNGKey(0) reproduces _build_arms'
    # weights exactly, so both arms prune to the identical pattern
    int8 = prepare_servable(
        init_model(jax.random.PRNGKey(0), cfg), cfg,
        ServingSpec(tile=TILE, sparsity=SPARSITY, prune="tied",
                    targets=TARGETS, backend="plan", pack_quant="int8"))

    # -- fidelity: teacher-forced next-token agreement over a prompt
    # batch (both arms see the IDENTICAL context at every position --
    # the standard quantization-fidelity metric; free-running decode
    # cascades a single flip into every later token) plus the raw max
    # logit delta and the model's own top-2 logit margins, so the
    # agreement number can be read against the decision margins it is
    # up against (random-init logits are near-tied by construction;
    # docs/PERF.md §Quantized packs)
    import jax.numpy as jnp
    toks = np.random.RandomState(6).randint(0, cfg.vocab_size, (8, 24))
    y32 = np.asarray(fp32.forward(jnp.asarray(toks)))
    y8 = np.asarray(int8.forward(jnp.asarray(toks)))
    max_delta = float(np.abs(y32 - y8).max())
    a32, a8 = y32.argmax(-1), y8.argmax(-1)
    tf_agreement = float((a32 == a8).mean())
    top2 = np.sort(y32, -1)
    gaps = top2[..., -1] - top2[..., -2]
    margin_stats = {"top2_gap_median": round(float(np.median(gaps)), 5),
                    "top2_gap_p10": round(float(np.percentile(gaps, 10)),
                                          5)}

    def greedy_tokens(servable):
        eng = servable.engine(max_slots=slots, cache_len=bp["cache_len"],
                              sync_every=sync_every, temperature=0.0)
        prng = np.random.RandomState(7)
        lens = [max(2, bp["prompt_len"] - (i % 4))
                for i in range(2 * slots)]
        reqs = [eng.submit(prng.randint(0, cfg.vocab_size, (L,)),
                           max_new_tokens=bp["max_new"]) for L in lens]
        eng.run()
        assert all(r.done for r in reqs)
        out = [list(r.tokens) for r in reqs]
        eng.close()
        return out

    t32, t8 = greedy_tokens(fp32), greedy_tokens(int8)
    matched = sum(a == b for s32, s8 in zip(t32, t8)
                  for a, b in zip(s32, s8))
    total = sum(len(s) for s in t32)
    fr_agreement = matched / max(total, 1)

    # -- bytes: fp32 vs int8+scales, total and per device ----------------
    b32_total, b32_dev = fp32.pack_bytes()
    b8_total, b8_dev = int8.pack_bytes()
    qs = int8.quant_stats() or {}
    bytes_cell = {
        "fp32_pack_bytes": b32_total, "fp32_pack_bytes_per_device": b32_dev,
        "int8_pack_bytes": b8_total, "int8_pack_bytes_per_device": b8_dev,
        "bytes_ratio": round(b32_total / max(b8_total, 1), 3),
        "compression_ratio": qs.get("compression_ratio"),
        "granularities": qs.get("granularities"),
        "max_abs_quant_err": qs.get("max_abs_err"),
    }
    emit(f"pack bytes: fp32 {b32_total}, int8 {b8_total} "
         f"({bytes_cell['bytes_ratio']}x smaller); "
         f"max |logit delta| {max_delta:.4g} "
         f"(model top-2 gap median {margin_stats['top2_gap_median']})")
    emit(f"greedy agreement: teacher-forced {tf_agreement:.2%}, "
         f"free-running engine {fr_agreement:.2%} "
         f"({matched}/{total} tokens)")

    # -- throughput: both arms through the fused engine loop -------------
    results = {}
    emit(f"{'arm':10s} {'tokens':>7s} {'sec':>8s} {'tok/s':>8s}")
    for name, servable in (("fp32_plan", fp32), ("int8_plan", int8)):
        _, cell = _run_cell(servable, slots, prompt_len=bp["prompt_len"],
                            max_new=bp["max_new"],
                            cache_len=bp["cache_len"], rng=rng,
                            reps=1 if smoke else 2, sync_every=sync_every)
        results[name] = [cell]
        emit(f"{name:10s} {cell['tokens']:7d} {cell['seconds']:8.3f} "
             f"{cell['tokens_per_s']:8.1f}")

    if write_json:
        section = "quant_error_smoke" if smoke else "quant_error"
        path = update_bench_json(section, {
            "model": cfg.arch, "layers": cfg.n_layers,
            "d_model": cfg.d_model, "sparsity": SPARSITY,
            "tile": list(TILE), "slots": slots, "sync_every": sync_every,
            "prompt_len": bp["prompt_len"], "max_new_tokens": bp["max_new"],
            "pack_quant": "int8",
            "pack_bytes": bytes_cell,
            "max_abs_logit_delta": round(max_delta, 6),
            "greedy_token_agreement": round(tf_agreement, 6),
            "engine_greedy_agreement": round(fr_agreement, 6),
            "logit_margins": margin_stats,
            "results": results,
        }, path=bench_path())
        emit(f"wrote {section} section to {path}")
    return results


#: positional selectors: `serving_bench.py --smoke run_open_loop` runs just
#: that section; no selector keeps the historical run-everything behavior
SELECTORS = ("run", "run_fused", "run_chaos", "run_kv_memory",
             "run_sharded", "run_open_loop", "run_quant_error")


def main(argv):
    smoke = "--smoke" in argv
    write_json = "--no-json" not in argv
    sweep = None
    if "--sync-every" in argv:
        sweep = tuple(int(v) for v in
                      argv[argv.index("--sync-every") + 1].split(","))
    mesh_sweep = None
    if "--mesh" in argv:
        mesh_sweep = tuple(int(v) for v in
                           argv[argv.index("--mesh") + 1].split(","))
    qps_sweep = None
    if "--qps" in argv:
        qps_sweep = tuple(float(v) for v in
                          argv[argv.index("--qps") + 1].split(","))
    chosen = [a for a in argv if a in SELECTORS]
    if "--skip-baseline" in argv and "run" in chosen:
        chosen.remove("run")

    def want(name):
        return name in chosen if chosen else True

    arms = None
    if any(want(n) for n in SELECTORS if n != "run_sharded"):
        arms = _build_arms(_bert_sized_lm(smoke), print)
    if want("run") and "--skip-baseline" not in argv:
        run(smoke=smoke, write_json=write_json, arms=arms)
    if want("run_fused"):
        run_fused(smoke=smoke, write_json=write_json, sync_sweep=sweep,
                  arms=arms)
    if want("run_chaos"):
        run_chaos(smoke=smoke, write_json=write_json, arms=arms)
    if want("run_kv_memory"):
        run_kv_memory(smoke=smoke, write_json=write_json, arms=arms)
    if want("run_open_loop"):
        run_open_loop(smoke=smoke, write_json=write_json, arms=arms,
                      qps_sweep=qps_sweep)
    if want("run_quant_error"):
        run_quant_error(smoke=smoke, write_json=write_json, arms=arms)
    if want("run_sharded"):
        run_sharded(smoke=smoke, write_json=write_json,
                    mesh_sweep=mesh_sweep)


if __name__ == "__main__":
    use_compile_cache()
    main(sys.argv[1:])
