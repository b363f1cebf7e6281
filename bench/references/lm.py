"""Llama-architecture decoder (DeepSeek-LLM 7B, arXiv:2401.02954): the
benchmark's weights and its plain float32 reference.

Per layer: RMSNorm -> multi-head attention with rotary embeddings
(rotate-half) -> residual -> RMSNorm -> SwiGLU FFN (silu(x Wg) * x Wi) Wo ->
residual; final RMSNorm and an untied LM head. No departures from the
published architecture; the RMSNorm weight is stored as an offset from 1,
which is the program's parameterization of the same function.

The reference runs layer by layer, regenerating each layer's weights from
the seed, so that it fits beside nothing: it runs after the program's
state is freed.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from references import common as C

RMS_EPS = 1e-6


def shapes(conf):
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    hd = conf["head_dim"]
    q, kv = conf["num_attention_heads"] * hd, conf["num_key_value_heads"] * hd
    return {"attn/wq": (q, d), "attn/wk": (kv, d), "attn/wv": (kv, d),
            "attn/wo": (d, q), "ffn/wi": (ff, d), "ffn/wg": (ff, d),
            "ffn/wo": (d, ff)}


def nnz_tiles(conf):
    tile, sp = tuple(conf["serving"]["tile"]), conf["serving"]["sparsity"]
    return {name: C.keep_tiles(shape, tile,
                               sp if name in conf["serving"]["targets"]
                               else 0.0)
            for name, shape in shapes(conf).items()}


def _keys(key):
    return jax.random.split(key)             # layers, top


def _masks(conf):
    tile, sp = tuple(conf["serving"]["tile"]), conf["serving"]["sparsity"]
    k_mask = C.mask_key(conf)
    return {name: C.draw_mask(jax.random.fold_in(k_mask, i), shape, tile, sp)
            for i, (name, shape) in enumerate(shapes(conf).items())
            if name in conf["serving"]["targets"]}


def _layer(conf, masks, kl, dtype):
    d = conf["hidden_size"]
    ws = {name: {"w": C.weight(jax.random.fold_in(kl, i), shape, dtype,
                               masks.get(name))}
          for i, (name, shape) in enumerate(shapes(conf).items())}
    return {"norm1": C.norm_params(jax.random.fold_in(kl, 10), d, dtype,
                                   False),
            "attn": {p: ws["attn/" + p] for p in ("wq", "wk", "wv", "wo")},
            "norm2": C.norm_params(jax.random.fold_in(kl, 11), d, dtype,
                                   False),
            "ffn": {p: ws["ffn/" + p] for p in ("wi", "wg", "wo")}}


def _top(conf, k_top, dtype):
    d, v = conf["hidden_size"], conf["vocab_size"]
    kt = [jax.random.fold_in(k_top, i) for i in range(3)]
    return {"embed": {"w": C.weight(kt[0], (v, d), dtype)},
            "final_norm": C.norm_params(kt[1], d, dtype, False),
            "lm_head": {"w": C.weight(kt[2], (v, d), dtype)}}


def init(conf, key, dtype=jnp.bfloat16):
    """The whole pruned parameter tree in the program's layout (one
    scan-stacked group of identical layers)."""
    k_layers, k_top = _keys(key)
    masks = _masks(conf)
    n = conf["num_hidden_layers"]
    stacked = C.stack_map(lambda kl: _layer(conf, masks, kl, dtype),
                          [jax.random.fold_in(k_layers, i) for i in range(n)])
    return dict(_top(conf, k_top, dtype), prefix=(), blocks=(stacked,),
                suffix=())


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _layer_params(conf_items, key, i, dtype):
    conf = _unfreeze(conf_items)
    k_layers, _ = _keys(key)
    return _layer(conf, _masks(conf),
                  jax.random.fold_in(k_layers, i), dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _top_params(conf_items, key, dtype):
    return _top(_unfreeze(conf_items), _keys(key)[1], dtype)


def _freeze(conf):
    """The configuration as a hashable static argument of the jits below."""
    return json.dumps(conf, sort_keys=True)


def _unfreeze(items):
    return json.loads(items)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_fwd(dims, lp, h, quant):
    """One layer over a batch of right-padded sequences h (B, T, d)."""
    n_heads, hd, theta = dims
    lp = C.f32(lp)
    b, t, _ = h.shape
    pos = jnp.arange(t)
    x = C.rms_norm(h, lp["norm1"], RMS_EPS)
    a = lp["attn"]

    def heads(y):
        return y.reshape(b, t, -1, hd)
    q, k, v = (heads(C.linear(x, a[n]["w"], quant))
               for n in ("wq", "wk", "wv"))
    q = jax.vmap(lambda y: C.rope(y, pos, theta))(q)
    k = jax.vmap(lambda y: C.rope(y, pos, theta))(k)
    o = jax.vmap(lambda *qkv: C.attention(*qkv, causal=True, q_block=256))(
        q, k, v).reshape(b, t, -1)
    h = h + C.linear(o, a["wo"]["w"], quant)
    x = C.rms_norm(h, lp["norm2"], RMS_EPS)
    f = lp["ffn"]
    g = jax.nn.silu(C.linear(x, f["wg"]["w"], quant)) * C.linear(
        x, f["wi"]["w"], quant)
    return h + C.linear(g, f["wo"]["w"], quant)


@functools.partial(jax.jit, static_argnums=(3,))
def _head(top, h, rows, quant):
    top = C.f32(top)
    x = C.rms_norm(h[rows], top["final_norm"], RMS_EPS)
    return C.linear(x, top["lm_head"]["w"], quant)


def logits(conf, key, seqs, rows, quant=None, dtype=jnp.bfloat16):
    """Float32 logits of each sequence at its ``rows`` positions.

    ``seqs``: list of int token arrays (prompt + served tokens); ``rows``:
    list of int arrays of positions. Sequences are right-padded to one
    length: causal attention keeps the padding out of every real position.
    Returns a list of device arrays (len(rows_i), V)."""
    frozen = _freeze(conf)
    t = max(len(s) for s in seqs)
    toks = jnp.asarray([list(s) + [0] * (t - len(s)) for s in seqs],
                       jnp.int32)
    top = _top_params(frozen, key, dtype)
    h = top["embed"]["w"][toks].astype(jnp.float32)
    dims = (conf["num_attention_heads"], conf["head_dim"],
            float(conf["rope_theta"]))
    for i in range(conf["num_hidden_layers"]):
        h = _layer_fwd(dims, _layer_params(frozen, key, i, dtype), h, quant)
    return [_head(top, h[j], jnp.asarray(r, jnp.int32), quant)
            for j, r in enumerate(rows)]
