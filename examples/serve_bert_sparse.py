"""End-to-end serving driver (the paper's deployment scenario): BERT_BASE
(110M params) answering batched requests through the block-sparse runtime,
driven entirely by the unified ``repro.serving`` API (docs/API.md).

One ``ServingSpec`` declares the whole co-design -- 80% block pruning at the
backend-optimal (128,128) tile (docs/PERF.md), tied masks, fused QKV (one
block-sparse dispatch per attention layer), cross-layer union packing (all
12 encoder layers share ONE specialization per projection group; the paper's
§2.2 task-buffer collapse) -- and ``prepare_servable`` runs prune -> BSR
export -> RowPackPlan -> registry caching in one call. The servable is then
saved and re-loaded (``load_servable``) to show that export cost is paid
once per model, and dense vs sparse serving is timed side by side. Results
are merged into BENCH_kernels.json (section "serving").

Tied masks (the default prune recipe) emulate the high inter-layer pattern
overlap the paper's small-block regularization produces -- that is what
keeps the cross-layer union tight (union overhead 1.0). Pass --no-tied to
prune each layer independently and watch the union fill in.

Run:  PYTHONPATH=src python examples/serve_bert_sparse.py [--requests 6]
          [--no-fused] [--no-union] [--no-tied] [--no-json] [--save DIR]
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.core.pruner import oneshot_prune, tied_prune
from repro.models import init_model, model_forward
from repro.runtime.bench_io import update_bench_json
from repro.runtime.compile_cache import use_compile_cache
from repro.serving import ServingSpec, load_servable, prepare_servable

SEQ, BATCH = 384, 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--sparsity", type=float, default=0.8)
    ap.add_argument("--tile", type=int, default=128)
    ap.add_argument("--no-fused", action="store_true",
                    help="three q/k/v dispatches per layer instead of one")
    ap.add_argument("--no-union", action="store_true",
                    help="one specialization per layer instead of one shared")
    ap.add_argument("--no-tied", action="store_true",
                    help="independent per-layer masks (loose union)")
    ap.add_argument("--no-json", action="store_true",
                    help="skip the BENCH_kernels.json serving section")
    ap.add_argument("--save", default=None, metavar="DIR",
                    help="persist the servable and re-serve via load_servable")
    args = ap.parse_args()
    use_compile_cache()

    print("initializing BERT_BASE (110M)...")
    cfg = get_config("bert_base")
    params = init_model(jax.random.PRNGKey(0), cfg)

    spec = ServingSpec(
        tile=(args.tile, args.tile), sparsity=args.sparsity,
        prune="oneshot" if args.no_tied else "tied",
        fuse_qkv=not args.no_fused, cross_layer_union=not args.no_union)
    # prune once here (the dense negative-control baseline below needs the
    # pruned dense tree too) and hand the facade pre-pruned weights
    prune = oneshot_prune if args.no_tied else tied_prune
    pruned, _ = prune(params, spec.sparsity_config())
    servable = prepare_servable(pruned, cfg,
                                dataclasses.replace(spec, prune="none"))
    st = servable.stats()
    print(f"pruned {args.sparsity:.0%} @ {args.tile}x{args.tile} "
          f"({spec.prune} masks); packed tile density {st['density']:.2f}")
    print(f"export: {st['packed_projections']} packed projections "
          f"({'fused QKV' if spec.fuse_qkv else 'unfused'}, "
          f"{'cross-layer union' if spec.cross_layer_union else 'per-layer'})")
    reg = st["registry"]
    print(f"pattern reuse: {reg['hits']} hits / {reg['misses']} misses "
          f"(reuse rate {reg['reuse_rate']:.0%}), {st['unique_patterns']} "
          f"unique patterns serve {st['packed_projections']} projections "
          f"across {cfg.n_layers} layers")
    if st["union_overhead"] is not None:
        print(f"cross-layer union overhead: {st['union_overhead']:.2f}x "
              f"(union tiles / mean per-layer tiles; 1.0 = perfectly tied)")

    if args.save:
        servable.save(args.save)
        servable = load_servable(args.save)
        print(f"saved + reloaded servable from {args.save} "
              f"(no re-export: registry_at_save="
              f"{servable.stats()['registry_at_save']})")

    # the dense baseline serves the SAME pruned weights without BSR support
    # (the paper's negative control)
    dense_fn = jax.jit(lambda p, t: model_forward(p, cfg, {"tokens": t})[0])
    rng = np.random.RandomState(0)
    reqs = [jnp.asarray(rng.randint(0, cfg.vocab_size, (BATCH, SEQ)))
            for _ in range(args.requests)]
    # warmup/compile
    jax.block_until_ready(dense_fn(pruned, reqs[0]))
    jax.block_until_ready(servable.forward(reqs[0]))

    times = {}
    for name, fn in (("dense", lambda r: dense_fn(pruned, r)),
                     ("BSR", servable.forward)):
        t0 = time.perf_counter()
        for r in reqs:
            out = fn(r)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / args.requests
        times[name] = dt
        print(f"{name:6s} serving: {dt*1e3:8.1f} ms/request")

    d = dense_fn(pruned, reqs[0])
    s = servable.forward(reqs[0])
    delta = float(jnp.max(jnp.abs(d - s)))
    print(f"parity: max |delta logits| = {delta:.2e}")

    if not args.no_json:
        path = update_bench_json("serving", {
            "model": cfg.arch, "seq": SEQ, "batch": BATCH,
            "requests": args.requests, "sparsity": args.sparsity,
            "tile": list(spec.tile), "fused_qkv": spec.fuse_qkv,
            "cross_layer_union": spec.cross_layer_union,
            "tied_masks": spec.prune == "tied",
            "dense_ms_per_request": round(times["dense"] * 1e3, 2),
            "sparse_ms_per_request": round(times["BSR"] * 1e3, 2),
            "speedup_vs_dense": round(times["dense"] / times["BSR"], 3),
            "max_abs_logit_delta": delta,
            "packed_tile_density": round(st["density"], 4),
            "union_overhead": (round(st["union_overhead"], 3)
                               if st["union_overhead"] is not None else None),
            "pattern_reuse": {**reg,
                              "unique_patterns": st["unique_patterns"],
                              "packed_projections":
                                  st["packed_projections"]},
        })
        print(f"wrote serving section to {path}")


if __name__ == "__main__":
    main()
