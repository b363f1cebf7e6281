"""Unified decoder-only LM covering dense / MoE / MLA / SSM / hybrid / VLM.

Layer stacks follow the config's ``layer_plan()``: an unrolled prefix, a
lax.scan over ``n_periods`` repetitions of the (possibly heterogeneous)
``pattern``, and an unrolled suffix. This keeps HLO size O(len(pattern)) no
matter how deep the model -- required for tractable 512-device compiles --
while still expressing per-layer heterogeneity (gemma3 5:1 local:global,
recurrentgemma 1:2 attn:recurrent) with static layer kinds.

The paper's technique is first-class: when serving params are exported via
the repro.serving facade (prepare_servable / serving.export), attention and
mixer projections route through the BSR kernels (pattern static + per-layer
packed values scanned).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerKind, ModelConfig
from repro.models import attention as attn
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (apply_mlp, apply_norm, init_mlp, init_norm,
                                 normal_init, paged_bulk_write)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, kind: LayerKind):
    ks = jax.random.split(key, 4)
    p = {"norm1": init_norm(ks[0], cfg.d_model, cfg.norm, cfg.jdtype)}
    if kind.mixer in ("attn", "local"):
        p["attn"] = attn.init_attention(ks[1], cfg)
    elif kind.mixer == "mla":
        p["attn"] = mla_mod.init_mla(ks[1], cfg)
    elif kind.mixer == "ssm":
        p["mixer"] = ssm_mod.init_ssm(ks[1], cfg)
    elif kind.mixer == "rglru":
        p["mixer"] = rglru_mod.init_rglru(ks[1], cfg)
    else:
        raise ValueError(kind.mixer)
    if kind.ffn == "dense":
        p["norm2"] = init_norm(ks[2], cfg.d_model, cfg.norm, cfg.jdtype)
        p["ffn"] = init_mlp(ks[3], cfg.d_model, cfg.d_ff, cfg.act, cfg.jdtype)
    elif kind.ffn == "moe":
        p["norm2"] = init_norm(ks[2], cfg.d_model, cfg.norm, cfg.jdtype)
        p["ffn"] = moe_mod.init_moe(ks[3], cfg)
    return p


def init_lm(key, cfg: ModelConfig):
    prefix, pattern, n_periods, suffix = cfg.layer_plan()
    k_embed, k_head, k_rest = jax.random.split(key, 3)
    params = {"embed": {"w": normal_init(k_embed, (cfg.vocab_size, cfg.d_model),
                                         0.02, cfg.jdtype)},
              "final_norm": init_norm(k_head, cfg.d_model, cfg.norm, cfg.jdtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": normal_init(
            jax.random.fold_in(k_head, 1), (cfg.vocab_size, cfg.d_model),
            0.02, cfg.jdtype)}

    keys = jax.random.split(k_rest, 3)
    params["prefix"] = tuple(
        _init_layer(jax.random.fold_in(keys[0], i), cfg, kind)
        for i, kind in enumerate(prefix))
    params["blocks"] = tuple(
        jax.vmap(lambda k, i=i, kind=kind: _init_layer(k, cfg, kind))(
            jax.random.split(jax.random.fold_in(keys[1], i), max(n_periods, 1)))
        for i, kind in enumerate(pattern)) if n_periods > 0 else ()
    params["suffix"] = tuple(
        _init_layer(jax.random.fold_in(keys[2], i), cfg, kind)
        for i, kind in enumerate(suffix))
    return params


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------

def _no_rows(cfg):
    """The MoE row counter of a layer that routes nothing."""
    return jnp.zeros((cfg.n_held,), jnp.int32)


def _real_tokens(b, s, pos, prefill_len):
    """(B, S) bool of the tokens a step really computes: decode rows of
    active slots, prefill positions below the prompt length (None: all)."""
    if pos is not None:
        return jnp.broadcast_to((attn.as_slot_positions(pos, b) >= 0)[:, None],
                                (b, s))
    if prefill_len is not None:
        return jnp.broadcast_to(jnp.arange(s)[None, :] < prefill_len, (b, s))
    return None


def _apply_layer(p, h, cfg, kind: LayerKind, *, positions, cache=None,
                 pos=None, packs=None, prefill_len=None, page_slot=None,
                 page_start=None, layer=None):
    """-> (h, new cache, aux loss, MoE rows (n_held,) int32)."""
    hn = apply_norm(p["norm1"], h, cfg.norm)
    aux = jnp.zeros((), jnp.float32)
    rows = _no_rows(cfg)
    mix_packs = _layer_packs(packs, "attn") or _layer_packs(packs, "mixer")
    if kind.mixer in ("attn", "local"):
        out, new_mix_cache = attn.apply_attention(
            p["attn"], hn, cfg, positions=positions, window=kind.window,
            cache=cache.get("mix") if cache else None, pos=pos,
            packs=mix_packs, prefill_len=prefill_len, page_slot=page_slot,
            page_start=page_start, layer=layer)
    elif kind.mixer == "mla":
        out, new_mix_cache = mla_mod.apply_mla(
            p["attn"], hn, cfg, positions=positions,
            cache=cache.get("mix") if cache else None, pos=pos,
            packs=mix_packs, prefill_len=prefill_len, page_slot=page_slot,
            page_start=page_start, layer=layer)
    elif kind.mixer == "ssm":
        out, new_mix_cache = ssm_mod.apply_ssm(
            p["mixer"], hn, cfg, cache=cache.get("mix") if cache else None,
            pos=pos, packs=mix_packs, prefill_len=prefill_len,
            page_slot=page_slot)
    elif kind.mixer == "rglru":
        out, new_mix_cache = rglru_mod.apply_rglru(
            p["mixer"], hn, cfg, cache=cache.get("mix") if cache else None,
            pos=pos, packs=mix_packs, prefill_len=prefill_len,
            page_slot=page_slot)
    # name the mixer output so the remat policy can pin it: the layer-body
    # recompute then skips re-running attention forward (saves ~2 of the 9
    # O(S^2) passes per layer; §Perf iter 4)
    from jax.ad_checkpoint import checkpoint_name
    out = checkpoint_name(out, "mixer_out")
    h = h + out

    if kind.ffn != "none" and "ffn" in p:
        hn = apply_norm(p["norm2"], h, cfg.norm)
        if kind.ffn == "moe":
            out, aux, rows = moe_mod.apply_moe(
                p["ffn"], hn, cfg, packs=_layer_packs(packs, "ffn"),
                valid=_real_tokens(*hn.shape[:2], pos, prefill_len),
                layer=layer)
        else:
            out = apply_mlp(p["ffn"], hn, cfg.act,
                            packs=_layer_packs(packs, "ffn"))
        h = h + out
    new_cache = {"mix": new_mix_cache} if cache is not None else None
    return h, new_cache, aux, rows


def _layer_packs(packs, scope):
    """Select this layer's packs: keys '<scope>/<name>' -> {'<name>': pack}."""
    if not packs:
        return None
    pre = scope + "/"
    sel = {k[len(pre):]: v for k, v in packs.items() if k.startswith(pre)}
    return sel or None


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, tokens, *, mm_embeds=None, packs=None):
    """tokens (B, S) -> logits (B, S, V) f32, aux loss."""
    prefix, pattern, n_periods, suffix = cfg.layer_plan()
    b, s = tokens.shape
    h = _embed(params, cfg, tokens)
    if mm_embeds is not None:   # vlm: patch embeddings occupy the prefix slots
        p = mm_embeds.shape[1]
        h = jnp.concatenate([mm_embeds.astype(h.dtype), h[:, p:]], axis=1)
    positions = jnp.arange(s)[None, :]
    aux = jnp.zeros((), jnp.float32)

    for i, kind in enumerate(prefix):
        h, _, a, _ = _apply_layer(params["prefix"][i], h, cfg, kind,
                                  positions=positions,
                                  packs=_layer_packs(packs, f"prefix/{i}"))
        aux += a

    if n_periods > 0:
        def body(carry, xs):
            h, aux = carry
            for i, kind in enumerate(pattern):
                h, _, a, _ = _apply_layer(
                    xs[i], h, cfg, kind, positions=positions,
                    packs=_layer_packs(packs, f"blocks/{i}"))
                aux += a
            return (h, aux), None
        # NOTE §Perf iter 4 (refuted): a save_only_these_names("mixer_out")
        # remat policy was tried to skip attention-forward recompute; the
        # custom-vjp residuals must be rebuilt either way, so flops stayed
        # flat (-0.8%) while temp memory rose 42%. Full-recompute remat wins.
        body = jax.checkpoint(body, prevent_cse=False)
        (h, aux), _ = jax.lax.scan(body, (h, aux), params["blocks"])

    for i, kind in enumerate(suffix):
        h, _, a, _ = _apply_layer(params["suffix"][i], h, cfg, kind,
                                  positions=positions,
                                  packs=_layer_packs(packs, f"suffix/{i}"))
        aux += a

    return _head(params, cfg, h), aux


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _init_layer_cache(cfg, kind: LayerKind, batch, cache_len, paged=None):
    if kind.mixer in ("attn", "local"):
        return {"mix": attn.init_cache_attn(cfg, batch, cache_len, kind.window,
                                            paged=paged)}
    if kind.mixer == "mla":
        return {"mix": mla_mod.init_cache_mla(cfg, batch, cache_len,
                                              paged=paged)}
    if kind.mixer == "ssm":
        return {"mix": ssm_mod.init_cache_ssm(cfg, batch)}
    if kind.mixer == "rglru":
        return {"mix": rglru_mod.init_cache_rglru(cfg, batch)}
    raise ValueError(kind.mixer)


def init_cache(cfg: ModelConfig, batch, cache_len, paged=None):
    """``paged`` (models.common.PagedLayout or None) switches every linear
    (window == 0) attention/MLA layer onto page-pool storage; ring caches and
    SSM/RgLRU state stay slot-dense regardless (their footprint is O(window)
    or O(1) per slot, so paging them buys nothing)."""
    prefix, pattern, n_periods, suffix = cfg.layer_plan()
    def stack(kind):
        one = _init_layer_cache(cfg, kind, batch, cache_len, paged)
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n_periods,) + x.shape), one)
    return {
        "prefix": tuple(_init_layer_cache(cfg, k, batch, cache_len, paged)
                        for k in prefix),
        "blocks": tuple(stack(k) for k in pattern) if n_periods > 0 else (),
        "suffix": tuple(_init_layer_cache(cfg, k, batch, cache_len, paged)
                        for k in suffix),
    }


# ---------------------------------------------------------------------------
# slot lifecycle (continuous batching: the batch dim is request slots)
#
# Cache leaves carry the slot dim at axis 0 in the unrolled prefix/suffix
# sections and at axis 1 in the scan-stacked ``blocks`` groups (leading dim =
# layer period), so the slot ops are defined here where that layout is known.
# ---------------------------------------------------------------------------

def _map_slot_sections(fn0, fn1, *caches):
    """Apply ``fn0`` (slot axis 0) / ``fn1`` (slot axis 1) leafwise across
    one or more structurally identical caches."""
    tmap = jax.tree_util.tree_map
    return {
        "prefix": tuple(tmap(fn0, *cs)
                        for cs in zip(*(c["prefix"] for c in caches))),
        "blocks": tuple(tmap(fn1, *cs)
                        for cs in zip(*(c["blocks"] for c in caches))),
        "suffix": tuple(tmap(fn0, *cs)
                        for cs in zip(*(c["suffix"] for c in caches))),
    }


def _is_pool_leaf(path):
    """True for ``*_pages`` leaves, whose axis 0 is *physical pages*, not
    request slots -- a slot-indexed op on them would corrupt page ``slot``."""
    name = getattr(path[-1], "key", None)
    return isinstance(name, str) and name.endswith("_pages")


def _split_pools(c):
    """One layer's cache -> (its ``*_pages`` pool leaves, the rest)."""
    pools = {k: v for k, v in c["mix"].items() if k.endswith("_pages")}
    return pools, {"mix": {k: v for k, v in c["mix"].items()
                           if k not in pools}}


def _join_pools(rest, pools):
    return {"mix": {**rest["mix"], **pools}}


#: a MoE layer's routed experts, the ``ffn`` leaves read by the layer index
_ROUTED = ("wi", "wg", "wo")


def _split_routed(p, kind: LayerKind):
    """One layer's params -> (them without the routed experts, the routed
    experts: empty but for a MoE layer)."""
    if kind.ffn != "moe" or "ffn" not in p:
        return p, {}
    ffn = p["ffn"]
    return ({**p, "ffn": {k: v for k, v in ffn.items() if k not in _ROUTED}},
            {k: ffn[k] for k in _ROUTED})


def _scan_blocks(params, cache, cfg: ModelConfig, h, packs, **layer_kw):
    """Run the scan-stacked ``blocks`` layers over ``h`` with their caches
    -> (h, new block caches). Page pools ride in the scan carry, stacked
    (P, n_pages, ...), and each layer reads and writes its own through the
    layer index: scanned as ``xs``/``ys`` instead, every layer of every
    step would slice its pool out and stack it back, whole-pool copies
    where one row per slot changes. The routed experts of MoE layers stay
    stacked in the same way, read at the layer index by ``moe_gmm``: as
    ``xs``, each layer would copy its experts out of the stack for the
    kernel. Everything else (weights, page tables, pos maps, rings,
    recurrent state) scans as ``xs``/``ys``."""
    _, pattern, n_periods, _ = cfg.layer_plan()
    pools, rest = zip(*map(_split_pools, cache["blocks"]))
    block_ps, routed = zip(*map(_split_routed, params["blocks"], pattern))

    def body(carry, xs):
        h, pools, rows = carry
        layer_ps, layer_cs, l = xs
        new_pools, new_cs = [], []
        for i, kind in enumerate(pattern):
            p = layer_ps[i]
            if routed[i]:
                p = {**p, "ffn": {**p["ffn"], **routed[i]}}
            h, c, _, r = _apply_layer(
                p, h, cfg, kind,
                cache=_join_pools(layer_cs[i], pools[i]), layer=l,
                packs=_layer_packs(packs, f"blocks/{i}"), **layer_kw)
            rows = rows + r
            p, c = _split_pools(c)
            new_pools.append(p)
            new_cs.append(c)
        return (h, tuple(new_pools), rows), tuple(new_cs)

    (h, pools, rows), rest = jax.lax.scan(
        body, (h, pools, _no_rows(cfg)),
        (block_ps, rest, jnp.arange(n_periods)))
    return h, tuple(map(_join_pools, rest, pools)), rows


def _layers(params, cache, cfg: ModelConfig, h, packs, **layer_kw):
    """Every layer over ``h`` with its cache: the unrolled prefix, the
    scanned blocks, the unrolled suffix -> (h, new cache, MoE rows summed
    over the layers)."""
    prefix, _, n_periods, suffix = cfg.layer_plan()

    def unrolled(sec, kinds, h, rows):
        out = []
        for i, kind in enumerate(kinds):
            h, c, _, r = _apply_layer(params[sec][i], h, cfg, kind,
                                      cache=cache[sec][i],
                                      packs=_layer_packs(packs, f"{sec}/{i}"),
                                      **layer_kw)
            rows = rows + r
            out.append(c)
        return h, tuple(out), rows

    h, new_prefix, rows = unrolled("prefix", prefix, h, _no_rows(cfg))
    new_blocks = cache["blocks"]
    if n_periods > 0:
        h, new_blocks, r = _scan_blocks(params, cache, cfg, h, packs,
                                        **layer_kw)
        rows = rows + r
    h, new_suffix, rows = unrolled("suffix", suffix, h, rows)
    return h, {"prefix": new_prefix, "blocks": new_blocks,
               "suffix": new_suffix}, rows


def _embed(params, cfg: ModelConfig, tokens):
    h = jnp.take(params["embed"]["w"], tokens, axis=0)
    if cfg.scale_embedding:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    return h


def _head(params, cfg: ModelConfig, h):
    h = apply_norm(params["final_norm"], h, cfg.norm)
    head = params["embed"]["w"] if cfg.tie_embeddings else params["lm_head"]["w"]
    return jnp.einsum("bsd,vd->bsv", h, head,
                      preferred_element_type=jnp.float32)


def reset_slot(cache, slot):
    """Zero request slot ``slot``: attention KV + pos_map AND the SSM/RgLRU
    recurrent and conv state, so a recycled slot cannot leak its previous
    request. Page pools are skipped (page hygiene is the allocator's job:
    ``pos_map``/``page_table`` reset to -1 here makes stale page content
    unreachable). Returns the updated cache (functional)."""
    reset = attn.slot_reset_value
    mp = jax.tree_util.tree_map_with_path
    f0 = lambda c: mp(lambda p, x: x if _is_pool_leaf(p)
                      else x.at[slot].set(reset(p, x[slot])), c)
    f1 = lambda c: mp(lambda p, x: x if _is_pool_leaf(p)
                      else x.at[:, slot].set(reset(p, x[:, slot])), c)
    return {"prefix": tuple(f0(c) for c in cache["prefix"]),
            "blocks": tuple(f1(c) for c in cache["blocks"]),
            "suffix": tuple(f0(c) for c in cache["suffix"])}


def write_slot(cache, slot, sub):
    """Insert single-request cache ``sub`` (batch == 1, e.g. a prefill
    result) into slot ``slot`` of the batched ``cache``."""
    return _map_slot_sections(lambda x, y: x.at[slot].set(y[0]),
                              lambda x, y: x.at[:, slot].set(y[:, 0]),
                              cache, sub)


def read_slot(cache, slot):
    """Extract slot ``slot`` as a batch-1 cache (the write_slot inverse)."""
    return _map_slot_sections(lambda x: x[slot:slot + 1],
                              lambda x: x[:, slot:slot + 1], cache)


# pool leaf -> the dense batch-1 sub-cache leaf that feeds it
_POOL_SRC = {"k_pages": "k", "v_pages": "v",
             "c_kv_pages": "c_kv", "k_rope_pages": "k_rope"}


def write_slot_paged(cache, slot, sub, page_row):
    """Insert a *dense* batch-1 prefill result ``sub`` into paged slot
    ``slot``: each pool leaf scatters the sub-cache rows page-by-page into
    the physical pages named by ``page_row`` (int32 (n_pages_per_slot,),
    -1 = unallocated -> dropped), the slot's ``page_table`` row becomes
    ``page_row`` and ``pos_map`` copies over. Every *allocated* page is
    fully written (sub content beyond the prompt is zeros), so recycled
    pages cannot leak stale or poisoned values. Non-paged leaves (rings,
    SSM/RgLRU state) take the ordinary dense slot write."""
    def ins(c, s, axis):
        m, ms = c["mix"], s["mix"]
        if "page_table" not in m:
            if axis == 0:
                return jax.tree_util.tree_map(
                    lambda x, y: x.at[slot].set(y[0]), c, s)
            return jax.tree_util.tree_map(
                lambda x, y: x.at[:, slot].set(y[:, 0]), c, s)
        out = {}
        for name, x in m.items():
            if name in _POOL_SRC:
                y = ms[_POOL_SRC[name]]
                if axis == 0:
                    out[name] = paged_bulk_write(x, page_row, y[0])
                else:                       # (P, n_pages, ps, ...) pools
                    out[name] = jax.vmap(
                        lambda pg, vl: paged_bulk_write(pg, page_row, vl)
                    )(x, y[:, 0])
            elif name == "page_table":
                if axis == 0:
                    out[name] = x.at[slot].set(page_row)
                else:
                    out[name] = x.at[:, slot].set(jnp.broadcast_to(
                        page_row, (x.shape[0],) + page_row.shape))
            else:                           # pos_map: plain dense insert
                out[name] = (x.at[slot].set(ms[name][0]) if axis == 0
                             else x.at[:, slot].set(ms[name][:, 0]))
        return {"mix": out}
    return {"prefix": tuple(ins(c, s, 0) for c, s in
                            zip(cache["prefix"], sub["prefix"])),
            "blocks": tuple(ins(c, s, 1) for c, s in
                            zip(cache["blocks"], sub["blocks"])),
            "suffix": tuple(ins(c, s, 0) for c, s in
                            zip(cache["suffix"], sub["suffix"]))}


def restore_slot_paged(cache, slot, page_row, resume_len):
    """Re-attach retained pages to slot ``slot`` after a preemption: write
    ``page_row`` back into the slot's page table and mark positions
    0..resume_len-1 live in ``pos_map``. Page *content* was never touched
    (refcounts held the pages out of the free list), so this restores the
    victim bit-exactly with zero prefill work. Paged layers only -- the
    engine gates retention to configs where every layer is paged."""
    def rst(c, axis):
        m = c["mix"]
        if "page_table" not in m:
            return c
        t = m["pos_map"].shape[-1]
        ar = jnp.arange(t)
        pm_row = jnp.where(ar < resume_len, ar, -1).astype(jnp.int32)
        out = dict(m)
        if axis == 0:
            out["page_table"] = m["page_table"].at[slot].set(page_row)
            out["pos_map"] = m["pos_map"].at[slot].set(pm_row)
        else:
            p = m["page_table"].shape[0]
            out["page_table"] = m["page_table"].at[:, slot].set(
                jnp.broadcast_to(page_row, (p,) + page_row.shape))
            out["pos_map"] = m["pos_map"].at[:, slot].set(
                jnp.broadcast_to(pm_row, (p, t)))
        return {"mix": out}
    return {"prefix": tuple(rst(c, 0) for c in cache["prefix"]),
            "blocks": tuple(rst(c, 1) for c in cache["blocks"]),
            "suffix": tuple(rst(c, 0) for c in cache["suffix"])}


def decode_step(params, cache, cfg: ModelConfig, token, pos, *, packs=None):
    """token (B, 1) + caches at absolute position ``pos`` -> (logits, cache,
    MoE rows).

    ``pos`` is a scalar (every row at the same position -- the single-request
    convention) or an int32 (B,) vector of ragged per-slot positions: each
    batch row is an independent request slot with its own causal/window mask
    and cache write slot. Rows with ``pos < 0`` are inactive -- their cache
    state is left untouched and their logits are meaningless. The MoE rows
    are (n_held,) int32 assignments each held expert computed, summed over
    the layers (zero length without MoE).
    """
    b = token.shape[0]
    h = _embed(params, cfg, token)
    pos = attn.as_slot_positions(pos, b)
    positions = jnp.maximum(pos, 0)[:, None]          # (B, 1), rope-safe
    h, new_cache, rows = _layers(params, cache, cfg, h, packs,
                                 positions=positions, pos=pos)
    return _head(params, cfg, h), new_cache, rows


def prefill_suffix(params, cache, cfg: ModelConfig, tokens, slot, start,
                   length=None, *, packs=None):
    """Prefill only the *suffix* ``tokens`` (1, S) of a prompt whose first
    ``start`` tokens are already resident in slot ``slot`` of the batched
    ``cache``: each layer writes the suffix KV (or carries recurrent state)
    at absolute positions start..start+length-1 for that slot and attends
    over resident-prefix + suffix with an explicit mask. Serves both the
    paged shared-prefix path (prefix-cache hit; PR 7) and dense-KV
    *chunked prefill* (docs/API.md §SLO scheduling), across every decode-
    capable mixer: global/windowed attention (dense rings + paged pools),
    MLA latents, SSM state carry, RG-LRU state carry. Sample the next
    token from ``logits[0, length - 1]`` after the final chunk. Returns
    (logits, cache, MoE rows) (:func:`decode_step`)."""
    s = tokens.shape[1]
    length = s if length is None else length
    start = jnp.asarray(start, jnp.int32)
    h, new_cache, rows = _layers(
        params, cache, cfg, _embed(params, cfg, tokens), packs,
        positions=start + jnp.arange(s)[None, :], prefill_len=length,
        page_slot=slot, page_start=start)
    return _head(params, cfg, h), new_cache, rows


def prefill_cache(params, cache, cfg: ModelConfig, tokens, length=None, *,
                  packs=None):
    """One-pass prompt prefill: ``tokens`` (B, S) starting at position 0 run
    through the *forward* attention/SSD/LRU paths (one weight stream for the
    whole prompt, not one per token), while every layer bulk-writes the
    state of positions 0..length-1 into ``cache``. ``length`` (<= S, traced
    OK) marks the real prompt; the tail is bucket padding and leaves no
    trace. Returns (logits (B, S, V) f32, cache, MoE rows)
    (:func:`decode_step`) -- sample the next token from
    ``logits[:, length - 1]``.
    """
    s = tokens.shape[1]
    h, new_cache, rows = _layers(
        params, cache, cfg, _embed(params, cfg, tokens), packs,
        positions=jnp.arange(s)[None, :],
        prefill_len=s if length is None else length)
    return _head(params, cfg, h), new_cache, rows
