"""Encoder cells: BERT served through ``prepare_servable`` and
``Servable.forward``, driven by a closed loop of one client.

The client sends a batch of token sequences, waits until its logits are
ready on the device, and sends the next; a request's latency is the host
clock around that. The timed path's logits of a sample of the window's
sequences (reservoir sampling drawn from the seed) are copied to the host
as they enter the sample, so that the device holds no more than serving
does, and compared with the plain float32 reference once the window has
closed.
"""
from __future__ import annotations

import functools
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from lib import traffic as T
from lib import work as W
from references import bert as ref

KERNELS = {"sparse_matmul": "plan_dds"}


def model_config(conf):
    from repro.configs.base import LayerKind, ModelConfig
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return ModelConfig(
        arch=conf["name"], family="bert", n_layers=conf["num_hidden_layers"],
        d_model=d, n_heads=h, n_kv_heads=h, head_dim=d // h,
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        norm="ln", act="gelu", rotary_fraction=0.0,
        pattern=(LayerKind("attn", "dense"),), dtype=conf["dtype"])


def serving_spec(conf):
    from repro.serving import ServingSpec
    s = conf["serving"]
    return ServingSpec(tile=tuple(s["tile"]), sparsity=s["sparsity"],
                       prune="none", targets=tuple(s["targets"]),
                       fuse_qkv=s["fuse_qkv"],
                       cross_layer_union=s["cross_layer_union"],
                       backend=s["backend"])


def weights(conf, seed):
    return jax.jit(functools.partial(ref.init, conf))(
        jax.random.PRNGKey(T.jax_seed(seed)))


@jax.jit
def _row(out, r):
    """Sequence ``r`` of a batch of logits, one program for every ``r``."""
    return jax.lax.dynamic_index_in_dim(out, r, keepdims=False)


def setup(run):
    from repro.serving import prepare_servable
    conf, mix = run.conf, run.mix
    with run.phase("init"):
        params = jax.block_until_ready(weights(conf, run.seed))
    with run.phase("export"):
        sv = prepare_servable(params, model_config(conf), serving_spec(conf))
        del params
        jax.block_until_ready(sv.params)
    run.log(f"packs: {sv.stats()['packed_projections']} projections, tile "
            f"density {sv.stats()['density']:.5f}")
    batches = T.token_batches(mix, run.seed, conf["vocab_size"])
    toks = [jax.device_put(b) for b in batches]
    with run.phase("compile"):
        jax.block_until_ready(sv.forward(toks[0]))
    with run.phase("warmup"):
        for x in toks[: min(len(toks), 4)]:
            out = jax.block_until_ready(sv.forward(x))
        np.asarray(_row(out, 0))
    return {"sv": sv, "toks": toks, "batches": batches}


def window(run, st, seconds):
    """The closed loop for ``seconds``; returns the run record."""
    fwd, toks, span = st["sv"].forward, st["toks"], run.span
    b, s = run.mix["batch"], run.mix["seq"]
    k = int(run.mix["check_samples"])
    pick = random.Random(T.jax_seed(run.seed) + 1)
    kept, lat = [], []
    copy_s = 0.0
    i = 0
    t0 = time.perf_counter()
    end = t0 + seconds
    t1 = t0
    while t1 < end:
        ts = time.perf_counter()
        j = i % len(toks)
        with span("bench.request"):
            out = jax.block_until_ready(fwd(toks[j]))
        t1 = time.perf_counter()
        lat.append(t1 - ts)
        for r in range(b):                      # reservoir sampling
            n = i * b + r
            slot = n if n < k else pick.randrange(n + 1)
            if slot < k:
                with span("bench.keep"):
                    got = (j, r, np.asarray(_row(out, r)))
                kept[slot:slot + 1] = [got]
        copy_s += time.perf_counter() - t1
        i += 1
    run.log(f"check sample: {len(kept)} sequences, copied to the host in "
            f"{copy_s:.3f} s of the window")
    return {"window_s": t1 - t0, "attempted": i, "sequences": i * b,
            "latencies_s": lat, "failed": 0, "kept": kept,
            "work": work(run.conf, i, b, s)}


def work(conf, n, b, s):
    """Required work of ``n`` requests of ``b`` x ``s`` tokens."""
    d, ff, v = (conf["hidden_size"], conf["intermediate_size"],
                conf["vocab_size"])
    tile = tuple(conf["serving"]["tile"])
    nnz = ref.nnz_tiles(conf)
    shp = ref.shapes(conf)
    m = b * s
    qkv = nnz["attn/wq"] + nnz["attn/wk"] + nnz["attn/wv"]
    packs = [W.packed_matmul(m, d, 3 * d, qkv, tile)] + [
        W.packed_matmul(m, shp[p][1], shp[p][0], nnz[p], tile)
        for p in ("attn/wo", "ffn/wi", "ffn/wo")]
    att = W.attention(s, s, conf["num_attention_heads"],
                      d // conf["num_attention_heads"],
                      conf["num_attention_heads"], causal=False)
    att = (att[0] * b, att[1] * b)
    layer = W.add(*packs, att)
    head = W.add(W.dense_matmul(m, d, d),
                 W.dense_matmul(m, d, v, obytes=4))
    L = conf["num_hidden_layers"]
    step = W.add(*([layer] * L), head)
    return {"flops": n * step[0], "steps": [(n, step)],
            "sparse_matmul": [(n * L, pk) for pk in packs]}


def e2e(rec):
    return {"seq_per_s": rec["sequences"] / rec["window_s"]}


def collect(run, st, rec):
    """The kept sequences' logits with their inputs, as batches of one."""
    return [(st["batches"][j][r:r + 1], out[None])
            for j, r, out in rec.pop("kept")]


def compare(run, samples, quant=None):
    """Numbers compared with the reference: the largest logit error of any
    sampled sequence, relative to that sequence's largest reference logit
    (``logit_err``), and the root-mean-square error over the sampled
    logits relative to their root-mean-square (``logit_rms_err``)."""
    conf = run.conf
    params = weights(conf, run.seed)
    fwd = jax.jit(functools.partial(ref.forward, conf),
                  static_argnames="quant")
    worst, num, den = 0.0, 0.0, 0.0
    for toks, out in samples:
        t = jnp.asarray(toks)
        r = fwd(params, t)
        # the control puts the quantized reference in the program's place
        o = fwd(params, t, quant=quant) if quant else out
        d = o - r
        worst = max(worst, float(jnp.max(jnp.abs(d)) / jnp.max(jnp.abs(r))))
        num += float(jnp.sum(d * d))
        den += float(jnp.sum(r * r))
    return {"logit_err": worst, "logit_rms_err": float(np.sqrt(num / den))}
