"""DeepSeek-V2-Lite cells: multi-head latent attention and the experts one
chip of an expert-parallel deployment holds, served through
``prepare_servable`` and the continuous-batching ``ServingEngine`` by the
decoder cells' closed loop (``families/lm.py``: the same clients, warm-up,
sampling of finished requests for the check and end-to-end metric).

What differs from a Llama decoder is the model (``model_config``), its
weights and float32 reference (``references/mla_moe.py``), and the
required work of a step: absorbed latent attention, a dense leading layer,
and per MoE layer the router, the held experts' grouped matmul
(``moe_gmm``) and the shared experts.
"""
from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from families import lm as LM
from lib import traffic as T
from lib import work as W
from lib import work_mla_moe as WM
from references import mla_moe as ref

KERNELS = {"sparse_matmul": "plan_dds", "moe_gmm": "moe_gmm"}
Loop, collect, e2e = LM.Loop, LM.collect, LM.e2e


def model_config(conf):
    from repro.configs.base import LayerKind, ModelConfig, YarnRope
    rs = conf["rope_scaling"]
    return ModelConfig(
        arch=conf["name"], family="moe",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["qk_nope_head_dim"],
        d_ff=conf["intermediate_size"],
        d_ff_expert=conf["moe_intermediate_size"],
        vocab_size=conf["vocab_size"],
        n_experts=conf["published"]["n_routed_experts"],
        experts_held=conf["n_routed_experts"],
        expert_offset=conf["expert_offset"],
        n_shared_experts=conf["n_shared_experts"],
        top_k=conf["num_experts_per_tok"],
        norm_topk_prob=conf["norm_topk_prob"],
        routed_scaling_factor=float(conf["routed_scaling_factor"]),
        kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_dim=conf["qk_nope_head_dim"],
        qk_rope_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"],
        rope_theta=float(conf["rope_theta"]),
        rope_yarn=YarnRope(
            factor=float(rs["factor"]),
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]), mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        prefix=(LayerKind("mla", "dense"),) * conf["first_k_dense_replace"],
        pattern=(LayerKind("mla", "moe"),), norm="rms", act="swiglu",
        dtype=conf["dtype"])


def weights(conf, seed):
    return jax.jit(functools.partial(ref.init, conf))(
        jax.random.PRNGKey(T.jax_seed(seed)))


def setup(run):
    """As ``families.lm.setup``, for this configuration."""
    from repro.serving import prepare_servable
    conf, mix = run.conf, run.mix
    cfg = model_config(conf)     # first: a program without MLA + MoE fails
    with run.phase("init"):
        params = jax.block_until_ready(weights(conf, run.seed))
    with run.phase("export"):
        sv = prepare_servable(params, cfg, LM.serving_spec(conf))
        del params
        jax.block_until_ready(sv.params)
    st = sv.stats()
    run.log(f"packs: {st['packed_projections']} projections, tile density "
            f"{st['density']:.5f}")
    with run.phase("compile"):
        eng = sv.engine(max_slots=mix["clients"], cache_len=mix["cache_len"],
                        sync_every=conf["serving"]["sync_every"],
                        kv_layout=conf["serving"]["kv_layout"])
        for prompt, n in LM._warm_requests(mix, eng.sync_every,
                                           conf["vocab_size"]):
            h = eng.submit(prompt, max_new_tokens=n)
            eng.run()
            if h.status != "done":
                raise RuntimeError(f"warm-up request ended {h.status}: "
                                   f"{h.failure}")
    with run.phase("warmup"):
        loop = Loop(eng, mix, run.seed, conf["vocab_size"])
        while len(loop.finished) < mix["lead_in"]:
            loop.step(run.span)
        loop.log, loop.finished = [], []
    return {"sv": sv, "eng": eng, "loop": loop}


def window(run, st, seconds):
    """As ``families.lm.window``, with this configuration's work."""
    eng, loop, span = st["eng"], st["loop"], run.span
    s0 = eng.stats
    c0 = (s0.tokens_generated, s0.prefill_s, s0.decode_s, s0.steps)
    first = max(h.req_id for h in loop.clients) + 1
    t0 = time.monotonic()
    end = t0 + seconds
    while time.monotonic() < end:
        loop.step(span)
    t1 = time.monotonic()
    s1 = eng.stats
    reqs = [h for h in loop.finished if h.req_id >= first] + \
        [h for h in loop.clients if h.req_id >= first]
    ttft = []
    for h in reqs:
        if h.status in ("failed", "shed", "cancelled"):
            ttft.append(math.inf)
        elif h.first_token_at is not None:
            ttft.append(h.first_token_at - h.submitted_at)
        else:
            ttft.append(t1 - h.submitted_at)
    done = [h for h in loop.finished
            if h.status == "done" and t0 <= h.finished_at <= t1]
    tpot = [(h.finished_at - h.first_token_at) / (len(h.tokens) - 1)
            for h in done if len(h.tokens) > 1]
    return {"window_s": t1 - t0, "attempted": len(reqs),
            "failed": sum(h.status != "done" for h in loop.finished),
            "tokens": s1.tokens_generated - c0[0],
            "prefill_s": s1.prefill_s - c0[1],
            "decode_s": s1.decode_s - c0[2],
            "engine_steps": s1.steps - c0[3],
            "ttft_s": ttft, "tpot_s": tpot, "done": done,
            "work": work(run.conf, run.mix, loop.log)}


def _forward_work(conf):
    """Per forward: (packed projections [(layers, K, N, nnz)], flops and
    weight bytes per token of the dense projections, of the router)."""
    m = ref.dims(conf)
    shp, nnz = ref.shapes(conf), ref.nnz_tiles(conf)
    n_moe = m["L"] - m["dense"]
    layers = {"attn/wq": m["L"], "attn/wo": m["L"], "attn/w_dkv": m["L"],
              "attn/w_krope": m["L"], "ffn/wi": m["dense"],
              "ffn/wg": m["dense"], "ffn/wo": m["dense"],
              "ffn/shared/wi": n_moe, "ffn/shared/wg": n_moe,
              "ffn/shared/wo": n_moe}
    packed = [(layers[n], shp[n][1], shp[n][0], z) for n, z in nnz.items()]
    dense = [(layers[n], shp[n][0] * shp[n][1]) for n in layers
             if n not in nnz]
    dense_flops = sum(2.0 * n * k for n, k in dense)
    dense_bytes = sum(2.0 * n * k for n, k in dense)
    router = (2.0 * n_moe * m["d"] * m["E"], 4.0 * n_moe * m["d"] * m["E"])
    return packed, (dense_flops, dense_bytes), router


def work(conf, mix, log):
    """Required work of every decode sub-step and prefill in ``log`` (the
    entries of ``families.lm.work``): per forward of m tokens, the packed
    projections, the dense ones (the latent and rope-key down-projections,
    layer 0's FFN where the tile does not divide it), the router, the held
    experts (``moe_gmm``), latent attention (absorbed in decode: the
    up-projections' weights once; expanded in prefill), the latent cache
    written, and the LM head for the rows sampled."""
    m = ref.dims(conf)
    L, n_moe, tile = m["L"], m["L"] - m["dense"], \
        tuple(conf["serving"]["tile"])
    h, r, dn, dr, dv = m["h"], m["r"], m["dn"], m["dr"], m["dv"]
    packed, dense, router = _forward_work(conf)
    up = h * (dn + dv) * r                      # w_uk and w_uv elements
    kv_pos = (r + dr) * 2 * L                   # latent cache bytes/token

    def forward(tokens, rows, attn):
        """(step work, packed-matmul entries, moe_gmm entry)."""
        spm = [(n, W.packed_matmul(tokens, k, nn, z, tile))
               for n, k, nn, z in packed]
        gmm = WM.moe_gmm(tokens, m["k"], m["held"], m["E"], m["d"],
                         m["f"])
        head = W.dense_matmul(rows, m["d"], m["V"], obytes=4)
        flops = (sum(n * w[0] for n, w in spm) + tokens * dense[0]
                 + tokens * router[0] + n_moe * gmm[0]
                 + 2.0 * tokens * up * L + attn[0] * L + head[0])
        nbytes = (sum(n * w[1] for n, w in spm) + dense[1] + router[1]
                  + n_moe * gmm[1] + 2.0 * up * L + attn[1] * L
                  + head[1] + tokens * kv_pos)
        return (flops, nbytes), spm, (n_moe, gmm)

    steps, spm, gmm = [], [], []
    for entries in log:
        for (p, idx0, n_dec, pre) in entries:
            if pre:
                w, s, g = forward(p, 1, WM.mla_prefill(p, h, dn + dr, dv))
                steps.append((1, w))
                spm += s
                gmm.append(g)
        depth = max([e[2] for e in entries] + [0])
        for k in range(depth):
            ctx = [e[0] + e[1] + k for e in entries if e[2] > k]
            da = [WM.mla_decode(c, h, r, dr) for c in ctx]
            w, s, g = forward(len(ctx), len(ctx),
                              (sum(x[0] for x in da), sum(x[1] for x in da)))
            steps.append((1, w))
            spm += s
            gmm.append(g)
    return {"steps": steps, "sparse_matmul": spm, "moe_gmm": gmm,
            "flops": sum(c * w[0] for c, w in steps)}


def compare(run, samples, quant=None):
    """``logit_gap`` as ``families.lm.compare``, against this
    configuration's reference (with ``quant``, the control)."""
    if not samples:
        return {"logit_gap": math.inf, "logit_gap_mean": math.inf}
    conf = run.conf
    key = jax.random.PRNGKey(T.jax_seed(run.seed))
    seqs = [np.concatenate([p, np.asarray(t[:-1], np.int32)])
            for p, t in samples]
    rows = [np.arange(p.size - 1, p.size + len(t) - 1) for p, t in samples]
    refs = ref.logits(conf, key, seqs, rows)
    if quant:
        ctrl = ref.logits(conf, key, seqs, rows, quant=quant)
        picks = [jnp.argmax(c, axis=-1) for c in ctrl]
    else:
        picks = [jnp.asarray(t, jnp.int32) for _, t in samples]
    gaps = []
    for r, tok in zip(refs, picks):
        got = jnp.take_along_axis(r, tok[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(jnp.max(r, axis=-1) - got))
    gaps = np.concatenate(gaps)
    return {"logit_gap": float(gaps.max()),
            "logit_gap_mean": float(gaps.mean()),
            "gap_tokens": int(gaps.size)}
