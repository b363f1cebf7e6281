"""Decoder cells: a Llama-architecture LM served through
``prepare_servable`` and the continuous-batching ``ServingEngine``, driven
by a closed loop of ``clients`` clients over as many engine slots.

Each client submits its next request as soon as its last one ends. The
harness steps the engine itself and, after every step, notes how many
tokens each request gained: from that log it counts the required work of
every decode step and prefill in the window. Once the window has closed, a
sample of the requests it finished (the longest among them) is compared
with the plain float32 reference, teacher-forced on the served tokens.
"""
from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from lib import traffic as T
from lib import work as W
from references import lm as ref

KERNELS = {"sparse_matmul": "plan_dds", "decode_attn": "flash_call"}
TERMINAL = ("done", "failed", "cancelled", "shed")
_PACKS = (("attn/wqkv", None), ("attn/wo", "attn/wo"), ("ffn/wi", "ffn/wi"),
          ("ffn/wg", "ffn/wg"), ("ffn/wo", "ffn/wo"))


def model_config(conf):
    from repro.configs.base import LayerKind, ModelConfig
    return ModelConfig(
        arch=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]), norm="rms", act="swiglu",
        pattern=(LayerKind("attn", "dense"),), dtype=conf["dtype"],
        q_chunk=conf.get("q_chunk", 1024), kv_chunk=conf.get("kv_chunk", 1024))


def serving_spec(conf):
    from repro.serving import ServingSpec
    s = conf["serving"]
    return ServingSpec(tile=tuple(s["tile"]), sparsity=s["sparsity"],
                       prune="none", targets=tuple(s["targets"]),
                       fuse_qkv=s["fuse_qkv"], backend=s["backend"],
                       kv_layout=s["kv_layout"],
                       kv_page_size=s["kv_page_size"],
                       decode_kernel=s["decode_kernel"])


def weights(conf, seed):
    return jax.jit(functools.partial(ref.init, conf))(
        jax.random.PRNGKey(T.jax_seed(seed)))


class Loop:
    """The closed loop: one request in flight per client."""

    def __init__(self, eng, mix, seed, vocab):
        self.eng, self.mix = eng, mix
        self.stream = T.request_stream(mix, seed, vocab)
        self.clients = [self.submit() for _ in range(mix["clients"])]
        self.log = []                  # per step: [(P, idx0, n_dec, prefill)]
        self.finished = []

    def submit(self):
        prompt, n = next(self.stream)
        return self.eng.submit(prompt, max_new_tokens=n)

    def step(self, span):
        before = [(h, h.status, len(h.tokens)) for h in self.clients]
        with span("bench.step"):
            self.eng.step()
        entries = []
        for h, st0, n0 in before:
            n1 = len(h.tokens)
            pre = st0 == "queued" and n1 > 0
            n_dec = n1 - n0 - (1 if pre else 0)
            if pre or n_dec > 0:
                entries.append((int(h.prompt.size), n0 + (1 if pre else 0),
                                n_dec, pre))
        self.log.append(entries)
        with span("bench.submit"):
            for c, h in enumerate(self.clients):
                if h.status in TERMINAL:
                    self.finished.append(h)
                    self.clients[c] = self.submit()


def _warm_requests(mix, sync_every, vocab):
    """Requests served alone before the window, so that every program the
    mix can reach is compiled: prompts at both ends of the length range and
    at each side of every power of two inside it (the engine pads prompts
    to power-of-two buckets: these reach each one), and decode windows of
    every length 1..sync_every."""
    p = mix["prompt"]
    lo, hi = (p["value"], p["value"]) if p.get("dist") == "fixed" else \
        (p["min"], p["max"])
    lens = {lo, hi}
    k = 1
    while k <= hi:
        lens |= {n for n in (k, k + 1) if lo <= n <= hi}
        k *= 2
    lens = sorted(lens)
    g = T.rng(0, 7)
    for i in range(max(len(lens), sync_every)):
        yield (g.integers(0, vocab, (lens[i % len(lens)],), dtype=np.int32),
               1 + 1 + i % sync_every)


def setup(run):
    from repro.serving import prepare_servable
    conf, mix = run.conf, run.mix
    with run.phase("init"):
        params = jax.block_until_ready(weights(conf, run.seed))
    with run.phase("export"):
        sv = prepare_servable(params, model_config(conf), serving_spec(conf))
        del params
        jax.block_until_ready(sv.params)
    st = sv.stats()
    run.log(f"packs: {st['packed_projections']} projections, tile density "
            f"{st['density']:.5f}, decode kernel {sv.decode_kernel_kind()}")
    with run.phase("compile"):
        eng = sv.engine(max_slots=mix["clients"], cache_len=mix["cache_len"],
                        sync_every=conf["serving"]["sync_every"],
                        kv_layout=conf["serving"]["kv_layout"])
        for prompt, n in _warm_requests(mix, eng.sync_every,
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


def _layer_work(conf):
    """(flops per token, weight bytes, per-pack (K, N, nnz)) of one layer's
    packed projections."""
    tile = tuple(conf["serving"]["tile"])
    nnz, shp = ref.nnz_tiles(conf), ref.shapes(conf)
    packs = []
    for name, src in _PACKS:
        if src is None:
            qkv = ("attn/wq", "attn/wk", "attn/wv")
            n = sum(shp[p][0] for p in qkv)
            packs.append((shp["attn/wq"][1], n, sum(nnz[p] for p in qkv)))
        else:
            packs.append((shp[src][1], shp[src][0], nnz[src]))
    tiles = sum(p[2] for p in packs)
    return 2.0 * tiles * tile[0] * tile[1], tiles * tile[0] * tile[1] * 2, \
        packs


def work(conf, mix, log):
    """Required work of every decode sub-step and prefill in ``log``.

    A step's entries are (prompt length P, index of the first token this
    step decoded, decode tokens, prefilled this step). In a fused window a
    request that gained n tokens was active in the window's first n
    sub-steps; the token of index i attends to P + i cached positions."""
    L, d, v = conf["num_hidden_layers"], conf["hidden_size"], \
        conf["vocab_size"]
    h, hd, hkv = conf["num_attention_heads"], conf["head_dim"], \
        conf["num_key_value_heads"]
    tile = tuple(conf["serving"]["tile"])
    f_tok, w_bytes, packs = _layer_work(conf)
    head = W.dense_matmul(1, d, v, obytes=4)
    kv_pos = 2 * hkv * hd * 2                      # K and V, bf16, per layer
    steps, spm, attn = [], [], []
    for entries in log:
        for (p, idx0, n_dec, pre) in entries:
            if pre:
                att = W.attention(p, p, h, hd, hkv, causal=True)
                steps.append((1, (p * f_tok * L + att[0] * L + head[0],
                                  w_bytes * L + head[1]
                                  + p * kv_pos * L)))
                spm += [(L, W.packed_matmul(p, k, n, z, tile))
                        for k, n, z in packs]
        depth = max([e[2] for e in entries] + [0])
        for k in range(depth):
            ctx = [e[0] + e[1] + k for e in entries if e[2] > k]
            m = len(ctx)
            da = [W.decode_attention(c, h, hd, hkv) for c in ctx]
            da = (sum(x[0] for x in da), sum(x[1] for x in da))
            steps.append((1, (m * (f_tok * L + head[0]) + da[0] * L,
                              w_bytes * L + d * v * 2 + da[1] * L
                              + m * kv_pos * L)))
            spm += [(L, W.packed_matmul(m, kk, n, z, tile))
                    for kk, n, z in packs]
            attn.append((L, da))
    return {"steps": steps, "sparse_matmul": spm, "decode_attn": attn,
            "flops": sum(c * w[0] for c, w in steps)}


def e2e(rec):
    return {"tok_per_s": rec["tokens"] / rec["window_s"]}


def collect(run, st, rec):
    """The longest request the window finished and others drawn from the
    seed, until ``check_tokens`` served tokens are covered."""
    done = sorted(rec.pop("done"), key=lambda h: h.req_id)
    if not done:
        return []
    g = T.rng(run.seed, 3)
    longest = max(done, key=lambda h: h.prompt.size + len(h.tokens))
    rest = [done[i] for i in g.permutation(len(done))
            if done[i] is not longest]
    picked, n = [], 0
    for h in [longest] + rest:
        if n >= run.mix["check_tokens"] or len(picked) >= run.mix[
                "check_max_requests"]:
            break
        picked.append((np.asarray(h.prompt), list(h.tokens)))
        n += len(h.tokens)
    return picked


def compare(run, samples, quant=None):
    """``logit_gap``: the widest gap by which a served token's reference
    logit lies below the reference's best at that position. With
    ``quant``, the control: the tokens that the quantized reference puts
    first at the same positions take the served tokens' place."""
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
