#!/usr/bin/env python3
"""Smoke run of the sparse serving paths on a TPU chip, through the entry
points a user calls (``prepare_servable``, ``Servable.forward``,
``Servable.engine``), with random weights made from ``--seed``.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --chips 4       # tensor-parallel engine, 4 chips
    JAX_PLATFORMS=cpu PYTHONPATH=src python chip_smoke.py --cpu-rehearsal

One chip runs two phases:

  * BERT-base serving, the paper's scenario: the whole 12-layer 768-wide
    model (float32), 80% tied block pruning at tile 128x128, fused QKV,
    cross-layer union. Arms on the same pruned weights: ``plan_pallas``
    (compiled kernel), ``plan`` (XLA), int8 packs on ``plan_pallas``, and
    the dense negative control; 4 requests of 384 tokens, batch 1. Each
    arm's logits are checked against a dense forward at matmul precision
    "highest".
  * The continuous-batching LM engine on gemma3-4b at its published widths,
    cut to one 5-local:1-global layer period, with sparse attention packs
    at tile 128x128: 8 greedy requests into 4 slots, 16 new tokens each,
    fused windows of 8, for dense and paged KV with the flash and the XLA
    decode kernel. Every request must end ``done`` and flash tokens must
    equal XLA tokens.

``--chips 4`` runs only the same gemma3 period tensor-parallel over a
(1, 4) mesh and compares its greedy tokens with one chip.

The script exits non-zero on any failed check and when JAX finds no TPU;
the last line of a passing chip run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
``--cpu-rehearsal`` runs the same phases on tiny configs in Pallas
interpret mode on the CPU and never prints that line.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

BERT_SEQ, BERT_REQUESTS, BERT_SPARSITY = 384, 4, 0.8
#: max |logit - reference| over max |reference|: on the chip, float32
#: matmuls at DEFAULT precision take one bf16 MXU pass (8-bit mantissa)
#: compounded over 12 layers, and int8 packs add <= 1/254 per block
BERT_REL_TOL = 5e-2
LM_SLOTS, LM_REQUESTS, LM_NEW, LM_SYNC, LM_CACHE = 4, 8, 16, 8, 512
LM_SPARSITY = 0.7
ATTN = ("attn/wq", "attn/wk", "attn/wv", "attn/wo")


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def device_info():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def mosaic_calls(lowered) -> int:
    """Compiled Pallas kernels in a lowered program (interpret mode emits
    none: it lowers the kernel body to plain XLA ops)."""
    return lowered.as_text().count("tpu_custom_call")


# --------------------------------------------------------------------------
# BERT-base serving
# --------------------------------------------------------------------------

def bert_phase(cfg, tile, seed, kind, compiled):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.pruner import tied_prune
    from repro.models import api as model_api
    from repro.serving import ServingSpec, prepare_servable

    params = model_api.init_model(jax.random.PRNGKey(seed), cfg)
    spec = ServingSpec(tile=tile, sparsity=BERT_SPARSITY, prune="tied",
                       fuse_qkv=True, cross_layer_union=True)
    pruned, _ = tied_prune(params, spec.sparsity_config())
    rng = np.random.RandomState(seed)
    reqs = [jnp.asarray(rng.randint(0, cfg.vocab_size, (1, BERT_SEQ)))
            for _ in range(BERT_REQUESTS)]
    fwd = lambda p, t, packs=None: model_api.model_forward(
        p, cfg, {"tokens": t}, packs=packs)[0]
    with jax.default_matmul_precision("highest"):
        ref_fn = jax.jit(fwd)
        refs = [np.asarray(ref_fn(pruned, r)) for r in reqs]
    scale = max(float(np.max(np.abs(r))) for r in refs)
    print(f"bert {cfg.arch}: {cfg.n_layers}L x {cfg.d_model}, {cfg.dtype}, "
          f"{BERT_SPARSITY:.0%} tied pruning at tile {tile[0]}x{tile[1]}, "
          f"{BERT_REQUESTS} requests x {BERT_SEQ} tokens; reference max "
          f"|logit| {scale:.6g}", flush=True)

    dense_fn = jax.jit(fwd)
    arms = [("dense", None, lambda r: dense_fn(pruned, r))]
    for name, kw in (("plan_pallas", dict(backend="plan_pallas")),
                     ("plan", dict(backend="plan")),
                     ("plan_pallas_int8", dict(backend="plan_pallas",
                                               pack_quant="int8"))):
        t0 = time.perf_counter()
        sv = prepare_servable(pruned, cfg, dataclasses.replace(
            spec, prune="none", **kw))
        st = sv.stats()
        print(f"bert {name}: export {time.perf_counter() - t0:.3f} s, "
              f"{st['packed_projections']} packed projections, tile "
              f"density {st['density']:.4f}", flush=True)
        arms.append((name, sv, sv.forward))

    for name, sv, fn in arms:
        if sv is not None:
            n_mosaic = mosaic_calls(jax.jit(
                lambda p, t, packs=sv.packs: fwd(p, t, packs)).lower(
                    sv.params, reqs[0]))
            want_kernels = compiled and name.startswith("plan_pallas")
            check((n_mosaic > 0) == want_kernels,
                  f"bert {name}: {n_mosaic} compiled Pallas calls in the "
                  f"lowered forward (expected "
                  f"{'some' if want_kernels else 'none'})")
        out, first_s = timed(lambda: fn(reqs[0]))
        outs, steady_s = timed(lambda: [fn(r) for r in reqs])
        err = max(float(np.max(np.abs(np.asarray(o) - r)))
                  for o, r in zip(outs, refs))
        finite = all(bool(np.isfinite(np.asarray(o)).all()) for o in outs)
        print(f"bert {name} [{kind}]: first call {first_s:.3f} s (compile "
              f"included), steady {steady_s / len(reqs) * 1e3:.3f} "
              f"ms/request, max |dlogit| vs highest-precision dense "
              f"{err:.4e} (rel {err / scale:.4e})", flush=True)
        check(finite and out.shape == refs[0].shape,
              f"bert {name}: non-finite or mis-shaped logits {out.shape}")
        check(err <= BERT_REL_TOL * scale,
              f"bert {name}: max |dlogit| {err:.4e} exceeds "
              f"{BERT_REL_TOL} x {scale:.4e}")


# --------------------------------------------------------------------------
# LM engine
# --------------------------------------------------------------------------

def lm_config():
    """gemma3-4b at its published widths, cut to one layer period."""
    from repro.configs import gemma3_4b
    full = gemma3_4b.full()
    return dataclasses.replace(full, n_layers=len(full.pattern))


def lm_prompts(cfg, seed):
    import numpy as np
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (20 + 5 * i,)).tolist()
            for i in range(LM_REQUESTS)]


def run_engine(sv, prompts, **kw):
    eng = sv.engine(max_slots=LM_SLOTS, cache_len=LM_CACHE,
                    sync_every=LM_SYNC, **kw)
    hs = [eng.submit(p, max_new_tokens=LM_NEW) for p in prompts]
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0
    bad = [(h.req_id, h.status, h.failure) for h in hs if h.status != "done"]
    check(not bad, f"requests not done: {bad}")
    toks = [list(h.tokens) for h in hs]
    check(all(len(t) == LM_NEW for t in toks),
          f"token counts {[len(t) for t in toks]} != {LM_NEW}")
    return eng, toks, dt


def first_divergence(a, b):
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            j = next(k for k, (u, v) in enumerate(zip(x, y)) if u != v)
            return f"request {i} step {j}: {x[j:j + 4]} vs {y[j:j + 4]}"
    return "none"


def describe_lm(cfg, full_layers):
    import jax
    from repro.models import api as model_api
    shapes = jax.eval_shape(
        lambda: model_api.init_model(jax.random.PRNGKey(0), cfg))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    print(f"lm {cfg.arch}: cut {full_layers} -> {cfg.n_layers} layers (one "
          f"{len(cfg.pattern) - 1}-local:1-global period); published widths "
          f"d {cfg.d_model}, {cfg.n_heads} q / {cfg.n_kv_heads} kv heads, "
          f"head_dim {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, {cfg.dtype}; {n} params", flush=True)


def lm_servable(pruned, cfg, tile, **kw):
    from repro.serving import ServingSpec, prepare_servable
    spec = ServingSpec(tile=tile, sparsity=LM_SPARSITY, prune="none",
                       targets=ATTN, **kw)
    t0 = time.perf_counter()
    sv = prepare_servable(pruned, cfg, spec)
    return sv, time.perf_counter() - t0


def lm_pruned(cfg, tile, seed):
    import jax
    from repro.core.pruner import oneshot_prune
    from repro.models import api as model_api
    from repro.serving import ServingSpec
    params = model_api.init_model(jax.random.PRNGKey(seed), cfg)
    spec = ServingSpec(tile=tile, sparsity=LM_SPARSITY, prune="oneshot",
                       targets=ATTN)
    return oneshot_prune(params, spec.sparsity_config())[0]


def lm_phase(cfg, tile, seed, kind, compiled):
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_decode import decode_kernel_override
    from repro.models import api as model_api

    pruned = lm_pruned(cfg, tile, seed)
    prompts = lm_prompts(cfg, seed)
    tokens = {}
    for dk in ("flash", "xla"):
        sv, export_s = lm_servable(pruned, cfg, tile, decode_kernel=dk)
        check(sv.decode_kernel_kind() == dk,
              f"servable resolved decode kernel {sv.decode_kernel_kind()}")
        print(f"lm decode_kernel={dk}: export {export_s:.3f} s, "
              f"{sv.stats()['packed_projections']} packed projections",
              flush=True)
        cache = sv.init_cache(LM_SLOTS, LM_CACHE)
        tok = jnp.zeros((LM_SLOTS, 1), jnp.int32)
        pos = jnp.zeros((LM_SLOTS,), jnp.int32)

        def step(p, c, t, s, packs=sv.packs):
            with decode_kernel_override(dk):
                return model_api.decode_step(p, c, cfg, t, s, packs=packs)
        n_mosaic = mosaic_calls(jax.jit(step).lower(sv.params, cache, tok,
                                                    pos))
        want = compiled and dk == "flash"
        check((n_mosaic > 0) == want,
              f"lm {dk}: {n_mosaic} compiled Pallas calls in the lowered "
              f"decode step (expected {'some' if want else 'none'})")
        del cache
        for layout in ("dense", "paged"):
            _, toks0, first_s = run_engine(sv, prompts, kv_layout=layout)
            eng, toks, steady_s = run_engine(sv, prompts, kv_layout=layout)
            check(toks == toks0, f"lm {layout}/{dk}: rerun tokens differ")
            n = eng.stats.tokens_generated
            print(f"lm {layout}/{dk} [{kind}]: first run {first_s:.3f} s "
                  f"(compile included), steady {n / steady_s:.3f} tok/s "
                  f"({n} tokens, {eng.stats.windows} windows, "
                  f"{steady_s:.3f} s)", flush=True)
            tokens[(layout, dk)] = toks
        del sv
    for layout in ("dense", "paged"):
        a, b = tokens[(layout, "flash")], tokens[(layout, "xla")]
        check(a == b, f"lm {layout}: flash tokens differ from xla at "
                      f"{first_divergence(a, b)}")
    same = tokens[("dense", "xla")] == tokens[("paged", "xla")]
    print(f"lm: flash == xla greedy tokens for dense and paged KV; dense == "
          f"paged: {same}", flush=True)


# --------------------------------------------------------------------------
# tensor-parallel engine (--chips 4)
# --------------------------------------------------------------------------

def tp_phase(cfg, tile, seed, kind, n):
    import jax

    pruned = lm_pruned(cfg, tile, seed)
    prompts = lm_prompts(cfg, seed)
    # the decode kernel is XLA on both sides: the Pallas flash kernel has
    # no partitioning rule, so a mesh program cannot split it over heads
    sv1, _ = lm_servable(pruned, cfg, tile, decode_kernel="xla")
    _, toks1, first1 = run_engine(sv1, prompts)
    eng1, toks1b, steady1 = run_engine(sv1, prompts)
    check(toks1 == toks1b, "tp: one-chip rerun tokens differ")
    del sv1, eng1

    svn, export_s = lm_servable(pruned, cfg, tile, decode_kernel="xla",
                                mesh_shape=(1, n), partition="tp")
    sh = svn.stats()["sharding"]
    print(f"tp (1, {n}): export {export_s:.3f} s, {sh['sharded_packs']} "
          f"packs sharded, pack bytes {sh['pack_bytes_per_device']}/device "
          f"of {sh['pack_bytes_total']} total", flush=True)
    check(sh["sharded_packs"] > 0, "tp: no pack was sharded")
    check(sh["pack_bytes_per_device"] <= 1.05 * sh["pack_bytes_total"] / n,
          f"tp: per-device pack bytes not ~1/{n} of the total")
    devices = set(jax.devices()[:n])
    for leaf in jax.tree_util.tree_leaves(svn.params):
        check(leaf.sharding.device_set == devices,
              f"tp: a param leaf {leaf.shape} sits on "
              f"{sorted(d.id for d in leaf.sharding.device_set)}")
    _, toksn, firstn = run_engine(svn, prompts)
    engn, toksn_b, steadyn = run_engine(svn, prompts)
    for leaf in jax.tree_util.tree_leaves(engn.cache):
        check(leaf.sharding.device_set == devices,
              f"tp: a cache leaf {leaf.shape} sits on "
              f"{sorted(d.id for d in leaf.sharding.device_set)}")
    ntok = engn.stats.tokens_generated
    for label, first_s, steady_s in (("1 chip", first1, steady1),
                                     (f"(1, {n}) mesh", firstn, steadyn)):
        print(f"tp {label} [{kind}]: first run {first_s:.3f} s (compile "
              f"included), steady {ntok / steady_s:.3f} tok/s", flush=True)
    check(toksn == toksn_b, "tp: mesh rerun tokens differ")
    check(toksn == toks1, f"tp: mesh tokens differ from one chip at "
                          f"{first_divergence(toksn, toks1)}")
    print(f"tp: greedy tokens on the (1, {n}) mesh equal one chip's",
          flush=True)


# --------------------------------------------------------------------------

def rehearsal_configs():
    """Tiny stand-ins with the real configs' structure for the CPU."""
    from repro.configs import bert_base, gemma3_4b
    lm = gemma3_4b.smoke()
    return (bert_base.smoke(), (16, 16),
            dataclasses.replace(lm, n_layers=len(lm.pattern)), (16, 16))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the tensor-parallel engine phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny configs in interpret mode; no chip result")
    args = ap.parse_args(argv)
    try:
        import jax
        from repro.runtime.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repo's package from "
              f"{os.path.join(ROOT, 'src')}: {e}", file=sys.stderr)
        return 2

    dev = device_info()
    kind = f"{dev['platform']}:{dev['kind']}"
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if args.cpu_rehearsal:
        bert_cfg, bert_tile, lm_cfg, lm_tile = rehearsal_configs()
        full_layers = lm_cfg.n_layers
    else:
        if dev["platform"] != "tpu":
            print(f"chip_smoke: JAX found no TPU (platform "
                  f"{dev['platform']!r}); refusing to run on it",
                  file=sys.stderr)
            return 1
        if dev["count"] < args.chips:
            print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                  f"devices, JAX sees {dev['count']}", file=sys.stderr)
            return 1
        from repro.configs import bert_base, gemma3_4b
        bert_cfg, bert_tile = bert_base.full(), (128, 128)
        lm_cfg, lm_tile = lm_config(), (128, 128)
        full_layers = gemma3_4b.full().n_layers
        print(f"compile cache: {use_compile_cache()}", flush=True)
    compiled = dev["platform"] == "tpu"

    t0 = time.perf_counter()
    try:
        describe_lm(lm_cfg, full_layers)
        if args.chips == 4:
            tp_phase(lm_cfg, lm_tile, args.seed, kind, 4)
        else:
            bert_phase(bert_cfg, bert_tile, args.seed, kind, compiled)
            lm_phase(lm_cfg, lm_tile, args.seed, kind, compiled)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"all phases passed in {time.perf_counter() - t0:.3f} s",
          flush=True)
    if args.cpu_rehearsal:
        print("cpu rehearsal only: no chip result", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
