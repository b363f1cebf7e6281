"""DeepSeek-V2-Lite (arXiv:2405.04434): the benchmark's weights and its
plain float32 reference, as one chip's share of an expert-parallel
deployment.

Per layer: RMSNorm -> multi-head latent attention -> residual -> RMSNorm ->
FFN -> residual; final RMSNorm and an untied LM head. Attention in its
expanded form: q = x Wq (no q-LoRA) split into a no-rope part (128 per
head) and a rope part (64); the latent c = RMSNorm(x Wdkv) (512) expands
to per-head keys c Wuk and values c Wuv; one rope key x Wkrope (64) is
shared by every head. Rope is YaRN's (factor 40 over 4096 original
positions, beta_fast 32, beta_slow 1); the softmax scale is 192^-0.5 times
mscale(40, 0.707)^2, cos and sin times mscale(40, 0.707) / mscale(40,
0.707) = 1. Layer 0's FFN is dense SwiGLU (10944); every later layer's is
MoE: softmax router over all 64 experts, the top 6 per token with their
probabilities as weights (not renormalized, scaled by 1), of which only
the 16 experts held here (``expert_offset`` ..) are computed, plus the 2
shared experts as one SwiGLU of 2 x 1408.

Departures: the published model de-interleaves the rope dims of q and of
the rope key before rotating them; with weights drawn from a seed that is
a fixed permutation of those projections' output columns, so it is left
out. Norm weights are stored as offsets from 1 (the program's
parameterization of the same function).

The reference runs layer by layer, regenerating each layer's weights from
the seed, and the experts one at a time, so that it fits beside nothing:
it runs after the program's state is freed.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from references import common as C

HI = jax.lax.Precision.HIGHEST


def dims(conf):
    return {"d": conf["hidden_size"], "h": conf["num_attention_heads"],
            "r": conf["kv_lora_rank"], "dn": conf["qk_nope_head_dim"],
            "dr": conf["qk_rope_head_dim"], "dv": conf["v_head_dim"],
            "F": conf["intermediate_size"],
            "f": conf["moe_intermediate_size"],
            "held": conf["n_routed_experts"],
            "E": conf["published"]["n_routed_experts"],
            "S": conf["n_shared_experts"], "k": conf["num_experts_per_tok"],
            "V": conf["vocab_size"], "L": conf["num_hidden_layers"],
            "dense": conf["first_k_dense_replace"]}


def shapes(conf):
    """(N, K) of each linear a layer holds; ``ffn/*`` of the dense layers,
    ``ffn/shared/*`` of the MoE ones."""
    m = dims(conf)
    d, h = m["d"], m["h"]
    sf = m["S"] * m["f"]
    return {"attn/wq": (h * (m["dn"] + m["dr"]), d),
            "attn/w_dkv": (m["r"], d), "attn/w_krope": (m["dr"], d),
            "attn/w_uk": (h * m["dn"], m["r"]),
            "attn/w_uv": (h * m["dv"], m["r"]),
            "attn/wo": (d, h * m["dv"]),
            "ffn/wi": (m["F"], d), "ffn/wg": (m["F"], d),
            "ffn/wo": (d, m["F"]),
            "ffn/shared/wi": (sf, d), "ffn/shared/wg": (sf, d),
            "ffn/shared/wo": (d, sf)}


def _divisible(shape, tile):
    return shape[0] % tile[0] == 0 and shape[1] % tile[1] == 0


def nnz_tiles(conf):
    """Tiles each pruned projection keeps; a projection the tile does not
    divide serves dense and is left out."""
    tile, sp = tuple(conf["serving"]["tile"]), conf["serving"]["sparsity"]
    return {n: C.keep_tiles(s, tile, sp) for n, s in shapes(conf).items()
            if n in conf["serving"]["targets"] and _divisible(s, tile)}


def _masks(conf):
    tile, sp = tuple(conf["serving"]["tile"]), conf["serving"]["sparsity"]
    k_mask = C.mask_key(conf)
    return {n: C.draw_mask(jax.random.fold_in(k_mask, i), s, tile, sp)
            for i, (n, s) in enumerate(shapes(conf).items())
            if n in nnz_tiles(conf)}


def _keys(key):
    return jax.random.split(key)             # layers, top


def _lin(conf, masks, kl, name, dtype):
    i = list(shapes(conf)).index(name)
    return {"w": C.weight(jax.random.fold_in(kl, i), shapes(conf)[name],
                          dtype, masks.get(name))}


def _layer(conf, masks, kl, dtype, moe: bool):
    m = dims(conf)
    attn = {p: _lin(conf, masks, kl, "attn/" + p, dtype)
            for p in ("wq", "w_dkv", "w_krope", "w_uk", "w_uv", "wo")}
    attn["kv_norm"] = {"scale": C.norm_params(jax.random.fold_in(kl, 32),
                                              m["r"], dtype, False)["scale"]}
    if moe:
        ke = [jax.random.fold_in(kl, 20 + i) for i in range(4)]
        ffn = {"router": C.weight(ke[0], (m["d"], m["E"]), jnp.float32),
               "wi": C.weight(ke[1], (m["held"], m["d"], m["f"]), dtype),
               "wg": C.weight(ke[2], (m["held"], m["d"], m["f"]), dtype),
               "wo": C.weight(ke[3], (m["held"], m["f"], m["d"]), dtype),
               "shared": {p: _lin(conf, masks, kl, "ffn/shared/" + p, dtype)
                          for p in ("wi", "wg", "wo")}}
    else:
        ffn = {p: _lin(conf, masks, kl, "ffn/" + p, dtype)
               for p in ("wi", "wg", "wo")}
    return {"norm1": C.norm_params(jax.random.fold_in(kl, 30), m["d"],
                                   dtype, False),
            "attn": attn,
            "norm2": C.norm_params(jax.random.fold_in(kl, 31), m["d"],
                                   dtype, False),
            "ffn": ffn}


def _top(conf, k_top, dtype):
    d, v = conf["hidden_size"], conf["vocab_size"]
    kt = [jax.random.fold_in(k_top, i) for i in range(3)]
    return {"embed": {"w": C.weight(kt[0], (v, d), dtype)},
            "final_norm": C.norm_params(kt[1], d, dtype, False),
            "lm_head": {"w": C.weight(kt[2], (v, d), dtype)}}


def init(conf, key, dtype=jnp.bfloat16):
    """The whole pruned parameter tree in the program's layout: the dense
    leading layers unrolled, the MoE layers one scan-stacked group."""
    k_layers, k_top = _keys(key)
    masks = _masks(conf)
    m = dims(conf)
    prefix = tuple(_layer(conf, masks, jax.random.fold_in(k_layers, i),
                          dtype, False) for i in range(m["dense"]))
    stacked = C.stack_map(
        lambda kl: _layer(conf, masks, kl, dtype, True),
        [jax.random.fold_in(k_layers, i) for i in range(m["dense"], m["L"])])
    return dict(_top(conf, k_top, dtype), prefix=prefix, blocks=(stacked,),
                suffix=())


def _freeze(conf):
    """The configuration as a hashable static argument of the jits below."""
    return json.dumps(conf, sort_keys=True)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _layer_params(frozen, key, i, moe, dtype):
    """Layer ``i``'s weights (``i`` traced: one compile serves every
    layer of a kind)."""
    conf = json.loads(frozen)
    k_layers, _ = _keys(key)
    return _layer(conf, _masks(conf), jax.random.fold_in(k_layers, i),
                  dtype, moe)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _top_params(frozen, key, dtype):
    return _top(json.loads(frozen), _keys(key)[1], dtype)


# -- YaRN, transcribed from the published DeepSeek-V2 modelling code ----------

def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(conf):
    """The rope frequencies of the ``qk_rope_head_dim`` dims."""
    rs, dim, base = (conf["rope_scaling"], conf["qk_rope_head_dim"],
                     float(conf["rope_theta"]))
    orig = rs["original_max_position_embeddings"]

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))
    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    extra = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    inter = extra / rs["factor"]
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def softmax_scale(conf):
    rs = conf["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def _rope(x, pos, inv, m):
    """Rotate-half rope of x (T, H, D) at positions pos (T,)."""
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :] * m, jnp.sin(ang)[:, None, :] * m
    d = x.shape[-1] // 2
    x1, x2 = x[..., :d], x[..., d:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- layers ---------------------------------------------------------------

RMS_EPS = 1e-6


def _swiglu(x, wi, wg, wo, quant):
    return C.linear(jax.nn.silu(C.linear(x, wg, quant))
                    * C.linear(x, wi, quant), wo, quant)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _attention(frozen, lp, h, quant):
    """h + MLA(RMSNorm(h)) for right-padded sequences h (B, T, d)."""
    conf = json.loads(frozen)
    m = dims(conf)
    hh, dn, dr, dv = m["h"], m["dn"], m["dr"], m["dv"]
    lp = C.f32(lp)
    a = lp["attn"]
    b, t, _ = h.shape
    pos = jnp.arange(t)
    inv = yarn_inv_freq(conf)
    rs = conf["rope_scaling"]
    mc = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(
        rs["factor"], rs["mscale_all_dim"])
    scale = softmax_scale(conf)

    def one(x):                                        # (T, d)
        q = C.linear(x, a["wq"]["w"], quant).reshape(t, hh, dn + dr)
        c = C.rms_norm(C.linear(x, a["w_dkv"]["w"], quant), a["kv_norm"],
                       RMS_EPS)
        kr = _rope(C.linear(x, a["w_krope"]["w"], quant)[:, None, :], pos,
                   inv, mc)                            # (T, 1, dr)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, inv, mc)],
                            -1)
        kn = C.linear(c, a["w_uk"]["w"], quant).reshape(t, hh, dn)
        v = C.linear(c, a["w_uv"]["w"], quant).reshape(t, hh, dv)
        k = jnp.concatenate([kn, jnp.broadcast_to(kr, (t, hh, dr))], -1)
        outs = []
        for s0 in range(0, t, 256):                    # query blocks
            qb = q[s0:s0 + 256]
            sc = jnp.einsum("shd,thd->hst", qb, k, precision=HI) * scale
            qpos = jnp.arange(s0, s0 + qb.shape[0])[:, None]
            sc = jnp.where(pos[None] <= qpos, sc, -1e30)
            outs.append(jnp.einsum("hst,thd->shd", jax.nn.softmax(sc, -1),
                                   v, precision=HI))
        return C.linear(jnp.concatenate(outs, 0).reshape(t, hh * dv),
                        a["wo"]["w"], quant)

    x = C.rms_norm(h, lp["norm1"], RMS_EPS)
    return h + jax.vmap(one)(x)


@functools.partial(jax.jit, static_argnums=(2,))
def _dense_ffn(lp, h, quant):
    lp = C.f32(lp)
    f = lp["ffn"]
    x = C.rms_norm(h, lp["norm2"], RMS_EPS)
    return h + _swiglu(x, f["wi"]["w"], f["wg"]["w"], f["wo"]["w"], quant)


@functools.partial(jax.jit, static_argnums=(0,))
def _route(frozen, lp, h):
    """(normed x, weight of each held expert per token (T.., held))."""
    conf = json.loads(frozen)
    m = dims(conf)
    x = C.rms_norm(h, C.f32(lp["norm2"]), RMS_EPS)
    probs = jax.nn.softmax(jnp.einsum("btd,de->bte", x, lp["ffn"]["router"],
                                      precision=HI), -1)
    top, idx = jax.lax.top_k(probs, m["k"])
    if conf["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * conf["routed_scaling_factor"]
    local = idx - conf["expert_offset"]
    held = jnp.arange(m["held"])
    w = jnp.sum(jnp.where(local[..., None] == held, top[..., None], 0.0),
                axis=-2)
    return x, w


@functools.partial(jax.jit, static_argnums=(5,))
def _expert(x, wi, wg, wo, w, quant):
    """One expert over every token, weighted by ``w`` (zero where it was
    not chosen)."""
    wi, wg, wo = (C.f32(a) for a in (wi, wg, wo))
    return w[..., None] * _swiglu(x, wi.T, wg.T, wo.T, quant)


@functools.partial(jax.jit, static_argnums=(3,))
def _shared(lp, x, h, quant):
    s = C.f32(lp["ffn"]["shared"])
    return h + _swiglu(x, s["wi"]["w"], s["wg"]["w"], s["wo"]["w"], quant)


@functools.partial(jax.jit, static_argnums=(3,))
def _head(top, h, rows, quant):
    top = C.f32(top)
    x = C.rms_norm(h[rows], top["final_norm"], RMS_EPS)
    return C.linear(x, top["lm_head"]["w"], quant)


def logits(conf, key, seqs, rows, quant=None, dtype=jnp.bfloat16):
    """Float32 logits of each sequence at its ``rows`` positions (as
    ``references.lm.logits``): right-padded sequences, causal attention
    keeps the padding out of every real position. The padded length is a
    multiple of 256, so that runs share the compiled reference."""
    frozen = _freeze(conf)
    m = dims(conf)
    t = -(-max(len(s) for s in seqs) // 256) * 256
    toks = jnp.asarray([list(s) + [0] * (t - len(s)) for s in seqs],
                       jnp.int32)
    top = _top_params(frozen, key, dtype)
    h = top["embed"]["w"][toks].astype(jnp.float32)
    for i in range(m["L"]):
        moe = i >= m["dense"]
        lp = _layer_params(frozen, key, i, moe, dtype)
        h = _attention(frozen, lp, h, quant)
        if not moe:
            h = _dense_ffn(lp, h, quant)
            continue
        x, w = _route(frozen, lp, h)
        f = lp["ffn"]
        for e in range(m["held"]):
            h = h + _expert(x, f["wi"][e], f["wg"][e], f["wo"][e],
                            w[..., e], quant)
        h = _shared(lp, x, h, quant)
    return [_head(top, h[j], jnp.asarray(r, jnp.int32), quant)
            for j, r in enumerate(rows)]
