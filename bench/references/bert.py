"""BERT-base encoder with the MLM head: the benchmark's weights and its plain
float32 reference (google-research/bert, "BERT: Pre-training of Deep
Bidirectional Transformers", arXiv:1810.04805).

Post-LN encoder: embeddings + learned positions -> LayerNorm; per layer
self-attention -> residual -> LayerNorm -> GELU FFN -> residual ->
LayerNorm; MLM head: dense -> GELU -> LayerNorm -> decoder tied to the
token embedding. Departures from the release, all shared with the program
under test: no biases on projections, no token-type embeddings, LayerNorm
eps 1e-6 (the release uses 1e-12) and the tanh form of GELU (the release
uses erf).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from references import common as C

LN_EPS = 1e-6
MAX_POSITIONS = 512


def shapes(conf):
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    return {"attn/wq": (d, d), "attn/wk": (d, d), "attn/wv": (d, d),
            "attn/wo": (d, d), "ffn/wi": (ff, d), "ffn/wo": (d, ff)}


def nnz_tiles(conf):
    """Stored tiles per projection after pruning (dense ones count all)."""
    tile, sp = tuple(conf["serving"]["tile"]), conf["serving"]["sparsity"]
    out = {}
    for name, shape in shapes(conf).items():
        s = sp if name in conf["serving"]["targets"] else 0.0
        out[name] = C.keep_tiles(shape, tile, s)
    return out


def init(conf, key, dtype=jnp.bfloat16):
    """The whole pruned parameter tree in the program's layout."""
    d, v = conf["hidden_size"], conf["vocab_size"]
    tile, sp = tuple(conf["serving"]["tile"]), conf["serving"]["sparsity"]
    k_layers, k_top = jax.random.split(key)
    k_mask = C.mask_key(conf)
    masks = {name: C.draw_mask(jax.random.fold_in(k_mask, i), shape, tile, sp)
             for i, (name, shape) in enumerate(shapes(conf).items())
             if name in conf["serving"]["targets"]}

    def layer(kl):
        ws = {name: {"w": C.weight(jax.random.fold_in(kl, i), shape, dtype,
                                   masks.get(name))}
              for i, (name, shape) in enumerate(shapes(conf).items())}
        return {"attn": {p: ws["attn/" + p] for p in ("wq", "wk", "wv", "wo")},
                "norm1": C.norm_params(jax.random.fold_in(kl, 10), d, dtype,
                                       True),
                "ffn": {p: ws["ffn/" + p] for p in ("wi", "wo")},
                "norm2": C.norm_params(jax.random.fold_in(kl, 11), d, dtype,
                                       True)}

    n = conf["num_hidden_layers"]
    stacked = C.stack_map(layer, [jax.random.fold_in(k_layers, i)
                                  for i in range(n)])
    kt = [jax.random.fold_in(k_top, i) for i in range(5)]
    return {
        "embed": {"w": C.weight(kt[0], (v, d), dtype)},
        "pos": C.weight(kt[1], (MAX_POSITIONS, d), dtype),
        "embed_norm": C.norm_params(kt[2], d, dtype, True),
        "layers": tuple(C.tree_index(stacked, i) for i in range(n)),
        "mlm_dense": {"w": C.weight(kt[3], (d, d), dtype)},
        "mlm_norm": C.norm_params(kt[4], d, dtype, True),
    }


def forward(conf, params, tokens, quant=None):
    """tokens (B, S) -> MLM logits (B, S, V), float32 throughout."""
    p = C.f32(params)
    h_heads = conf["num_attention_heads"]
    b, s = tokens.shape
    h = p["embed"]["w"][tokens] + p["pos"][None, :s]
    h = C.layer_norm(h, p["embed_norm"], LN_EPS)
    for lp in p["layers"]:
        a = lp["attn"]

        def heads(x):
            return x.reshape(b, s, h_heads, -1)
        q, k, v = (heads(C.linear(h, a[n]["w"], quant))
                   for n in ("wq", "wk", "wv"))
        o = jax.vmap(lambda *qkv: C.attention(*qkv, causal=False))(
            q, k, v).reshape(b, s, -1)
        h = C.layer_norm(h + C.linear(o, a["wo"]["w"], quant), lp["norm1"],
                         LN_EPS)
        f = C.gelu_tanh(C.linear(h, lp["ffn"]["wi"]["w"], quant))
        h = C.layer_norm(h + C.linear(f, lp["ffn"]["wo"]["w"], quant),
                         lp["norm2"], LN_EPS)
    t = C.gelu_tanh(C.linear(h, p["mlm_dense"]["w"], quant))
    t = C.layer_norm(t, p["mlm_norm"], LN_EPS)
    return C.linear(t, p["embed"]["w"], quant)
