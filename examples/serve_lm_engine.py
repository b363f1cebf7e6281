"""Continuous-batching LM serving demo: the request-level deployment story.

Builds a sparse Servable for a decoder-only LM, constructs the
continuous-batching engine (``servable.engine(...)``), and pushes a burst of
requests with mixed prompt lengths through a handful of request slots --
more requests than slots, so admission, bucketed prefill, ragged batched
decode, and slot recycling all run. Tokens stream per request through the
``on_token`` callback while the engine batches every active request into ONE
jitted decode *window* -- ``--sync-every`` fused steps between host syncs
(docs/API.md §Engine; ``--sync-every 1`` shows the per-step loop the fused
path replaced). ``--temperature``/``--top-k``/``--seed`` switch greedy
decoding to on-device seeded sampling.

``--tp N`` serves the same workload tensor-parallel over a ``(1, N)``
device mesh (spec ``mesh_shape``): BSR plan packs shard by output block
rows / input block cols, the slot cache shards its KV heads, and
``stats()`` reports the per-shard pack bytes and registry accounting. On
CPU, expose fake devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/serve_lm_engine.py --tp 8

Compare with examples/serve_bert_sparse.py (batched *encoder* serving):
this demo is the decode-side counterpart the paper's runtime argument
ultimately cares about -- concurrency without per-request graphs.

``--kv-layout paged`` serves the same burst from a paged KV pool with
radix prefix sharing; add ``--shared-prefix 32`` to give every request
one shared system prompt and watch ``stats_dict()['kv']`` report pool
utilization and the prompt tokens served from shared pages instead of
prefill (docs/API.md §Paged KV + prefix cache).

``--pack-quant int8`` serves the same packs with int8 block values +
per-block fp32 scales, dequant fused into the plan matmul
(docs/API.md §Quantized sparse packs). The demo prints a pack-bytes
scorecard -- fp32-equivalent vs quantized, per device under ``--tp N``
-- next to the tok/s line, so the memory/fidelity trade is visible in
one run.

Run:  PYTHONPATH=src python examples/serve_lm_engine.py
          [--arch deepseek_7b] [--slots 4] [--requests 10] [--max-new 12]
          [--sync-every 8] [--temperature 0.8] [--top-k 40] [--tp N]
          [--kv-layout paged] [--kv-page-size 16] [--shared-prefix 32]
          [--pack-quant int8]
"""
import argparse
import time

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.models import init_model
from repro.runtime.compile_cache import use_compile_cache
from repro.serving import ServingSpec, prepare_servable


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek_7b",
                    help="any decode-capable arch (smoke config is used)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--sparsity", type=float, default=0.7)
    ap.add_argument("--sync-every", type=int, default=8,
                    help="fused decode window length K (1 = per-step loop)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 samples on device")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards: serve over a (1, N) mesh "
                         "(needs N visible devices; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--kv-layout", default="dense",
                    choices=("dense", "paged"),
                    help="KV storage: 'paged' = page-pool KV + radix "
                         "prefix sharing (docs/API.md §Paged KV)")
    ap.add_argument("--kv-page-size", type=int, default=16)
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend one shared N-token system prompt to every "
                         "request -- with --kv-layout paged the prefix cache "
                         "serves the repeats from shared pages")
    ap.add_argument("--pack-quant", default="none",
                    choices=("none", "int8", "fp8"),
                    help="store pack values quantized with per-block "
                         "scales, dequant fused into the plan matmul "
                         "(docs/API.md §Quantized sparse packs)")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch, smoke=True)
    print(f"initializing {cfg.arch} ({cfg.family})...")
    params = init_model(jax.random.PRNGKey(0), cfg)
    servable = prepare_servable(params, cfg, ServingSpec(
        tile=(16, 16), sparsity=args.sparsity, prune="oneshot",
        targets=("attn/wq", "attn/wk", "attn/wv", "attn/wo"),
        mesh_shape=(1, args.tp) if args.tp > 1 else None, partition="tp",
        kv_layout=args.kv_layout, kv_page_size=args.kv_page_size,
        pack_quant=args.pack_quant))
    st = servable.stats()
    print(f"sparse export: {st['packed_projections']} packed projections, "
          f"density {st['density']:.2f}" if st["density"] is not None
          else "no packed projections (dense serving)")
    if args.tp > 1:
        sh = st["sharding"]
        print(f"tensor-parallel: mesh (1, {args.tp}), "
              f"{sh['sharded_packs']}/{st['packed_projections']} packs "
              f"sharded, pack bytes/device "
              f"{sh['pack_bytes_per_device']}/{sh['pack_bytes_total']} "
              f"(total)")
        hits = {s: f"{v['hits']}h/{v['misses']}m"
                for s, v in sorted(sh["per_shard_registry"].items())}
        print(f"per-shard registry (layout reuse across layers): {hits}")
    qs = servable.quant_stats()
    if qs:
        print(f"pack-bytes scorecard ({qs['qdtype']}, "
              f"{'/'.join(sorted(qs['granularities']))} scales):")
        print(f"  fp32-equivalent: {qs['fp32_equiv_bytes_total']:>10d} B "
              f"total, {qs['fp32_equiv_bytes_per_device']:>10d} B/device")
        print(f"  quantized:       {qs['quant_bytes_total']:>10d} B "
              f"total, {qs['quant_bytes_per_device']:>10d} B/device "
              f"(incl. {qs['scale_bytes_total']} B scales)")
        print(f"  compression {qs['compression_ratio']:.2f}x, worst "
              f"quant err {qs['max_abs_err']:.2e} abs / "
              f"{qs['max_rel_err']:.2e} rel")

    engine = servable.engine(max_slots=args.slots, cache_len=128,
                             sync_every=args.sync_every,
                             temperature=args.temperature,
                             top_k=args.top_k, seed=args.seed)
    rng = np.random.RandomState(0)

    streams = {}

    def on_token(rid, tok):
        streams.setdefault(rid, []).append(tok)

    def on_done(rid, toks):
        print(f"  request {rid}: done, {len(toks)} tokens -> {toks[:8]}"
              f"{'...' if len(toks) > 8 else ''}")

    system = rng.randint(0, cfg.vocab_size,
                         (args.shared_prefix,)).tolist()
    print(f"submitting {args.requests} requests "
          f"(prompts 3..18 tokens"
          + (f" after a shared {len(system)}-token system prompt"
             if system else "")
          + f") into {args.slots} slots...")
    handles = []
    for i in range(args.requests):
        prompt = system + rng.randint(
            0, cfg.vocab_size, (3 + (5 * i) % 16,)).tolist()
        handles.append(engine.submit(prompt, max_new_tokens=args.max_new,
                                     on_token=on_token, on_done=on_done))

    t0 = time.perf_counter()
    engine.run()
    dt = time.perf_counter() - t0

    s = engine.stats
    assert all(h.done for h in handles)
    assert all(streams[h.req_id] == h.tokens for h in handles)
    print(f"served {s.completed} requests / {s.tokens_generated} tokens in "
          f"{dt:.2f}s ({s.tokens_generated / dt:.1f} tok/s)")
    print(f"{s.steps} decode steps in {s.windows} fused windows "
          f"(sync_every={args.sync_every}), mean occupancy "
          f"{s.mean_occupancy:.2f}/{args.slots} slots, prefill buckets "
          f"{dict(s.bucket_hits)}")
    print(f"wall-clock breakdown: prefill {s.prefill_s:.2f}s, decode "
          f"{s.decode_s:.2f}s, host-sync {s.sync_s:.2f}s")
    kv = engine.stats_dict()["kv"]
    if kv["layout"] == "paged":
        print(f"kv pool: {kv['pages_used']}/{kv['n_pages']} pages used "
              f"(peak {kv['peak_pages_used']}, "
              f"page_size {kv['page_size']}, "
              f"utilization {kv['utilization']:.1%}), "
              f"{kv['kv_bytes_used']}/{kv['kv_bytes_total']} bytes")
        print(f"prefix sharing: {kv['prefix_hit_tokens']} prompt tokens "
              f"served from shared pages, {kv['prefilled_tokens']} "
              f"prefilled, {kv['prefix_cached_pages']} pages cached, "
              f"{kv['page_resumes']} page-retained resumes")
    else:
        print(f"kv (dense slots): {kv['kv_bytes_total']} bytes total, "
              f"{kv['kv_bytes_per_slot']} per slot")


if __name__ == "__main__":
    main()
