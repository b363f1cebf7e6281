"""Family-agnostic BSR export passes (the TVM relay-conversion analogue).

Training keeps dense weights + block masks (core.pruner). Serving packs the
pruned projections into tile-granular BSR and -- by default -- lowers each
pattern to a precomputed :class:`~repro.kernels.exec_plan.RowPackPlan`: the
pattern arrays become static plan metadata (cached through
``core.pattern_reuse.PatternRegistry``) and the servable param tree stores
the tile values *already row-grouped*, so the per-call path is pure compute
(docs/PERF.md).

Three pattern-level optimizations happen here, offline:

  * **plans** (``use_plans=True``): weight data is re-laid-out once at export
    instead of on every forward call;
  * **fused QKV** (``fuse_qkv=True``): the wq/wk/wv patterns are concatenated
    along N into a single pack, so attention issues ONE block-sparse matmul
    (one gather of x, one dispatch) per layer instead of three;
  * **cross-layer union** (``export_bert_sparse(cross_layer_union=True)``):
    the per-layer patterns of all encoder layers are unioned so a single
    specialization serves every layer with per-layer data -- the paper's §2.2
    task-buffer mechanism, collapsing 12 compilations to 1. For scan-stacked
    LM layer groups the same union machinery has always applied
    (``pack_stacked``). High inter-layer pattern overlap -- which the paper's
    small-block regularization promotes -- keeps the union tight;
    ``union_overhead`` quantifies the waste.

These passes are consumed by the :func:`repro.serving.prepare_servable`
facade (docs/API.md), which dispatches on ``cfg.family`` via
:func:`export_params`; the per-family entry points remain available for
callers that need one pass in isolation.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.pattern_reuse import PatternRegistry
from repro.kernels.bsr_matmul import KernelBSR, pack_bsr
from repro.kernels.exec_plan import (QuantPlan, build_sharded_plan,
                                     dequantize_plan_values, pack_plan_data,
                                     plan_for_pack, quantize_for_plan,
                                     shard_divisible)

# projection names exported per mixer/ffn kind
_ATTN_PROJS = ("wq", "wk", "wv", "wo")
_QKV = ("wq", "wk", "wv")
_FFN_PROJS = ("wi", "wg", "wo")


def shard_axis_for(proj: str) -> str:
    """Tensor-parallel axis per projection, mirroring the dense rules of
    ``launch/sharding.spec_for_param``: ``wo`` (attention out-proj AND MLP
    down-proj) is row-parallel -- sharded by input block cols, partials
    psum'd -- everything else (wq/wk/wv/wqkv/wi/wg) is column-parallel,
    sharded by output block rows."""
    return "in" if proj == "wo" else "out"

# families whose param tree follows the lm.py prefix/blocks/suffix layout
LM_FAMILIES = ("dense", "moe", "ssm", "hybrid", "vlm")


def _tile_mask(w: np.ndarray, tile) -> np.ndarray:
    n, k = w.shape
    bn, bk = tile
    return np.any(w.reshape(n // bn, bn, k // bk, bk) != 0, axis=(1, 3))


def pack_stacked(w_stacked: np.ndarray, tile) -> Tuple[KernelBSR, jax.Array, Dict]:
    """(L, N, K) -> (pattern pack, per-layer data (L, nnzt, bn, bk), stats)."""
    l, n, k = w_stacked.shape
    bn, bk = tile
    masks = np.stack([_tile_mask(w_stacked[i], tile) for i in range(l)])
    union = masks.any(axis=0)
    # build the pattern from a dense "ones at union" stand-in
    proto = np.kron(union.astype(np.float32), np.ones(tile, np.float32))
    pack = pack_bsr(proto, tile)
    rows = pack.row_id[: pack.nnzt]
    cols = pack.col_id
    blocks = w_stacked.reshape(l, n // bn, bn, k // bk, bk).transpose(0, 1, 3, 2, 4)
    data = blocks[:, rows, cols]                      # (L, nnzt, bn, bk)
    per_layer_nnz = masks.sum(axis=(1, 2))
    stats = {
        "union_nnzt": int(union.sum()),
        "mean_layer_nnzt": float(per_layer_nnz.mean()),
        "union_overhead": float(union.sum() / max(per_layer_nnz.mean(), 1.0)),
    }
    return pack, jnp.asarray(data), stats


def pack_single(w: np.ndarray, tile) -> Tuple[KernelBSR, jax.Array]:
    pack = pack_bsr(w, tile)
    return pack, pack.data


# --------------------------------------------------------------------------
# serving-form helpers (KernelBSR pattern -> plan + row-grouped values)
# --------------------------------------------------------------------------

def _realize_backend(pack, data, backend: str,
                     registry: Optional[PatternRegistry],
                     shard=None, shard_stats=None, quant: str = "none"):
    """(pattern, packed values, chosen backend) -> (static pack stored in
    ``packs``, values stored in the params tree). ``data`` is
    ``(nnzt, bn, bk)`` or layer-stacked ``(L, nnzt, bn, bk)``.

      * ``plan``    -> RowPackPlan + row-grouped values (the default path);
        with ``shard = (n_shards, axis)`` and a divisible pattern, a
        :class:`~repro.kernels.exec_plan.ShardedPlan` whose vrow axis is
        mesh-"model"-shardable (indivisible patterns fall back to the
        replicated plan, the ``spec_for_param`` divisibility rule);
      * ``plan_pallas`` -> the same row-grouped layout wrapped in a
        :class:`~repro.kernels.exec_plan.PlanChoice` pinning every call to
        the compiled plan-consuming Pallas kernel (no sharded form --
        ShardedPlan stays on the XLA 'plan' path);
      * ``bsr``     -> bare KernelBSR (runtime ``default_backend()``);
      * ``gather``/``rowpack``/``pallas`` -> the pattern pinned to that
        ``bsr_linear`` backend (``autotune.BackendChoice``);
      * ``masked``  -> dense-layout values + static tile mask
        (``autotune.MaskedPack``);
      * ``dense``   -> ``(None, None)``: the caller keeps the original
        dense weight and stores no pack (measurement said format support
        does not pay here).

    ``quant != 'none'`` (spec ``pack_quant``) quantizes the plan-layout
    backends: the values come back as a ``{"w": qvalues, "scale": scales}``
    dict wrapped in a :class:`~repro.kernels.exec_plan.QuantPlan` (other
    backends ignore it -- no per-block scale granularity to quantize at).
    The explicit ``plan_q8`` / ``plan_pallas_q8`` backends are the autotune
    verdict names for the same layouts.
    """
    if backend in ("plan", "plan_q8"):
        if shard is not None and shard[0] > 1 \
                and shard_divisible(pack, shard[0], shard[1]):
            # built (not combined-cached) per call so identical layers
            # still count per-shard registry hits; plans stay shared
            # downstream via fingerprint hash/eq
            plan = build_sharded_plan(pack, shard[0], shard[1],
                                      registry=registry,
                                      shard_stats=shard_stats)
        else:
            plan = plan_for_pack(pack, registry)
        if backend == "plan_q8" or (backend == "plan" and quant != "none"):
            return quantize_for_plan(plan, data,
                                     quant if quant != "none" else "int8",
                                     backend="plan")
        return plan, pack_plan_data(plan, data)
    if backend in ("plan_pallas", "plan_pallas_q8"):
        from repro.kernels.bsr_matmul import check_pallas_tile
        from repro.kernels.exec_plan import PlanChoice
        check_pallas_tile(pack.tile)
        plan = plan_for_pack(pack, registry)
        if backend == "plan_pallas_q8" or quant != "none":
            return quantize_for_plan(plan, data,
                                     quant if quant != "none" else "int8",
                                     backend="plan_pallas")
        return PlanChoice(plan), pack_plan_data(plan, data)
    if backend == "bsr":
        return pack, data
    if backend == "dense":
        return None, None
    from repro.kernels.autotune import (BackendChoice, dense_from_pack,
                                        masked_pack_from)
    if backend == "masked":
        data = np.asarray(jax.device_get(jnp.asarray(data)))
        if data.ndim == 4:      # (L, nnzt, bn, bk) -> (L, N, K)
            vals = np.stack([dense_from_pack(pack, d) for d in data])
        else:
            vals = dense_from_pack(pack, data)
        return masked_pack_from(pack), jnp.asarray(vals)
    if backend in ("gather", "rowpack", "pallas"):
        return BackendChoice(pack, backend), data
    raise ValueError(f"unknown serving backend {backend!r}")


def _effective_shard(pack, shard):
    """The shard config this pack will ACTUALLY serve under: None unless a
    mesh is active and the pattern divides -- keeps the autotune cache key
    (and candidate restriction) honest for replicated-fallback packs, and
    keeps single-argument ``backend_chooser`` callbacks working unsharded."""
    if shard is not None and shard[0] > 1 and shard_divisible(pack, *shard):
        return shard
    return None


def _choose(chooser, pack, shard):
    """Invoke a backend chooser, passing ``shard=`` only when this pack
    really shards (pre-mesh choosers take a single argument)."""
    return chooser(pack) if shard is None else chooser(pack, shard=shard)


def _quant_meta(pk, vals, data) -> Optional[Dict]:
    """Export-time quantization round-trip accounting for a QuantPlan pack:
    max abs dequant error over the stored tiles, absolute and relative to
    the pack's value range. Recorded in the export stats (and surfaced by
    ``Servable.stats()`` / ``stats_dict()``) so precision loss is visible
    where the byte savings are."""
    if not isinstance(pk, QuantPlan):
        return None
    ref = pack_plan_data(pk.plan, data)
    deq = dequantize_plan_values(vals["w"], vals["scale"])
    err = float(jnp.max(jnp.abs(deq - ref)))
    amax = float(jnp.max(jnp.abs(ref)))
    return {"qdtype": pk.qdtype, "granularity": pk.granularity,
            "max_abs_err": err,
            "rel_err": err / amax if amax > 0 else 0.0}


def _serving_pack(w: np.ndarray, tile, use_plans: bool,
                  registry: Optional[PatternRegistry], chooser=None,
                  shard=None, shard_stats=None, quant: str = "none"):
    """(N, K) weight -> (static pattern, values, autotune meta). With plans,
    the values are row-grouped once here -- the scatter the seed backend
    paid per call. A ``chooser`` (kernels/autotune.py) overrides the
    plan/bsr default with the measured winner for this pattern."""
    pack = pack_bsr(w, tile)
    shard = _effective_shard(pack, shard)
    if chooser is None:
        pk, vals = _realize_backend(pack, pack.data,
                                    "plan" if use_plans else "bsr", registry,
                                    shard, shard_stats, quant)
        qmeta = _quant_meta(pk, vals, pack.data)
        return pk, vals, {"quant": qmeta} if qmeta else None
    choice = _choose(chooser, pack, shard)
    pk, vals = _realize_backend(pack, pack.data, choice.backend, registry,
                                shard, shard_stats, quant)
    meta = {"backend": choice.backend,
            "cache_hit": choice.cache_hit, "mode": choice.mode}
    qmeta = _quant_meta(pk, vals, pack.data)
    if qmeta:
        meta["quant"] = qmeta
    return pk, vals, meta


def _serving_pack_stacked(w_stacked: np.ndarray, tile, use_plans: bool,
                          registry: Optional[PatternRegistry], chooser=None,
                          shard=None, shard_stats=None, quant: str = "none"):
    pack, data, stats = pack_stacked(w_stacked, tile)
    shard = _effective_shard(pack, shard)
    if chooser is None:
        pk, vals = _realize_backend(pack, data,
                                    "plan" if use_plans else "bsr", registry,
                                    shard, shard_stats, quant)
        qmeta = _quant_meta(pk, vals, data)
        if qmeta:
            stats = dict(stats, quant=qmeta)
        return pk, vals, stats
    choice = _choose(chooser, pack, shard)
    pk, vals = _realize_backend(pack, data, choice.backend, registry,
                                shard, shard_stats, quant)
    stats = dict(stats)
    stats["autotune"] = {"backend": choice.backend,
                         "cache_hit": choice.cache_hit, "mode": choice.mode}
    qmeta = _quant_meta(pk, vals, data)
    if qmeta:
        stats["quant"] = qmeta
    return pk, vals, stats


def _param_entry(vals, dtype) -> Dict:
    """Params-tree entry for a pack's serving values. Quantized packs come
    back as a ``{"w", "scale"}`` dict whose leaves keep their own dtypes
    (int8/fp8 values, fp32 scales -- the spec ``dtype`` cast must not touch
    them); everything else stores ``{"w": values}`` cast to the model
    dtype."""
    if isinstance(vals, dict):
        return dict(vals)
    return {"w": vals.astype(dtype)}


def _param_entry_layer(vals, i: int, dtype) -> Dict:
    """Per-layer slice of stacked serving values (bert unrolled-encoder
    path): index the leading layer axis of each leaf."""
    if isinstance(vals, dict):
        return {k: v[i] for k, v in vals.items()}
    return {"w": vals[i].astype(dtype)}


def _get_w(p) -> np.ndarray:
    return np.asarray(jax.device_get(p["w"]), np.float32)


def _divisible(shape, tile) -> bool:
    return shape[-2] % tile[0] == 0 and shape[-1] % tile[1] == 0


def _fused_qkv_weight(ap, tile, stacked: bool) -> Optional[np.ndarray]:
    """Concatenate wq/wk/wv along N (one pack, one dispatch); None when a
    projection is missing or a segment boundary would not land on a block
    row (each segment's N must divide the kernel tile's bn)."""
    if not all(proj in ap for proj in _QKV):
        return None
    ws = [_get_w(ap[proj]) for proj in _QKV]
    if not all(_divisible(w.shape, tile) for w in ws):
        return None
    return np.concatenate(ws, axis=1 if stacked else 0)


# --------------------------------------------------------------------------
# per-family export passes
# --------------------------------------------------------------------------

def _pack_nnzt(pk) -> Optional[int]:
    """Stored-tile count of any static pack kind (plan / bsr / choice /
    masked), for the per-scope export stats."""
    if pk is None:
        return None
    inner = getattr(pk, "pack", pk)             # BackendChoice wraps a BSR
    inner = getattr(inner, "plan", inner)       # PlanChoice wraps a plan
    if hasattr(inner, "real_nnzt"):
        return int(inner.real_nnzt)
    if hasattr(inner, "tile_mask"):
        return int(np.sum(inner.tile_mask))
    return None


def export_lm_sparse(params, cfg: ModelConfig, tile=(128, 128), *,
                     fuse_qkv: bool = True, use_plans: bool = True,
                     include_ffn: bool = True,
                     registry: Optional[PatternRegistry] = None,
                     backend_chooser=None, n_shards: int = 1,
                     pack_quant: str = "none"):
    """Replace attention (and pruned FFN) projections of an LM param tree
    with packed values.

    Returns (sparse_params, packs, stats): ``packs`` maps layer scopes
    ('blocks/<i>/<proj>', 'prefix/<i>/<proj>', ...) to static patterns
    (RowPackPlan by default, KernelBSR with ``use_plans=False``); forward()
    consumes them via the ``packs=`` argument. Scan-stacked layer groups are
    union-packed (one specialization, per-layer data); with ``fuse_qkv`` the
    q/k/v projections additionally share one fused pack per layer group.

    With ``include_ffn`` the dense-MLP projections (wi/wg/wo -- the paper's
    FC targets, where most decode FLOPs live) are exported too, but ONLY
    when actually block-sparse at the kernel tile: packing an unpruned
    (100%-density) projection is pure loss, so attention-only prune
    recipes serve their FFN dense exactly as before. In MoE layers the
    shared experts are packed the same way; the routed experts stay dense
    (the grouped expert matmul, kernels/moe_gmm.py).

    ``backend_chooser`` (spec ``backend='auto'``, kernels/autotune.py)
    overrides the representation per pattern with the measured winner; a
    ``dense`` verdict keeps the original weight (no pack) and is recorded
    in ``stats`` like every other choice.

    ``n_shards > 1`` (spec ``mesh_shape``) exports every plan pack in
    tensor-parallel sharded form (:func:`shard_axis_for` per projection;
    indivisible patterns fall back to replicated) and records per-shard
    registry accounting under ``stats['__sharding__']``.
    """
    packs: Dict[str, object] = {}
    stats: Dict[str, Dict] = {}
    shard_stats: Dict[int, Dict] = {}
    new = jax.tree_util.tree_map(lambda x: x, params)  # shallow copy-ish

    def _export_one(w, scope, stacked, proj):
        """Pack one weight (single or layer-stacked), record its stats
        under ``scope``, and register the pack. Returns the serving values,
        or None when the pattern serves dense (autotune verdict) -- the
        caller then keeps the original weight."""
        shard = (n_shards, shard_axis_for(proj)) if n_shards > 1 else None
        if stacked:
            pk, data, st = _serving_pack_stacked(
                w, tile, use_plans, registry, backend_chooser,
                shard, shard_stats, pack_quant)
        else:
            pk, data, meta = _serving_pack(
                w, tile, use_plans, registry, backend_chooser,
                shard, shard_stats, pack_quant)
            st = {"union_nnzt": _pack_nnzt(pk)}
            if meta:
                qmeta = meta.pop("quant", None)
                if qmeta:
                    st["quant"] = qmeta
                if meta:
                    st["autotune"] = meta
        stats[scope] = st
        if pk is None:
            return None
        packs[scope] = pk
        return data

    def export_attn(layer_params, scope, stacked):
        if "attn" not in layer_params:
            return layer_params
        ap = dict(layer_params["attn"])
        projs = list(_ATTN_PROJS)
        if fuse_qkv:
            w_qkv = _fused_qkv_weight(ap, tile, stacked)
            if w_qkv is not None:
                dtype = ap["wq"]["w"].dtype
                data = _export_one(w_qkv, f"{scope}/wqkv", stacked, "wqkv")
                if data is not None:
                    ap["wqkv"] = _param_entry(data, dtype)
                    for proj in _QKV:
                        del ap[proj]
                # measured dense: wq/wk/wv stay, unfused
                projs = ["wo"]
        for proj in projs:
            if proj not in ap:
                continue
            w = _get_w(ap[proj])
            if not _divisible(w.shape, tile):
                continue
            data = _export_one(w, f"{scope}/{proj}", stacked, proj)
            if data is not None:
                ap[proj] = _param_entry(
                    data, layer_params["attn"][proj]["w"].dtype)
        out = dict(layer_params)
        out["attn"] = ap
        return out

    def _is_sparse(w: np.ndarray, stacked: bool) -> bool:
        """True iff the (stacked-union) tile occupancy is < 100%: packing a
        dense projection only adds padding and gather overhead."""
        if stacked:
            occ = np.stack([_tile_mask(w[i], tile) for i in range(w.shape[0])]
                           ).any(axis=0)
        else:
            occ = _tile_mask(w, tile)
        return bool(occ.mean() < 1.0)

    def export_mlp(mp, scope, stacked):
        """The dense MLP tree ``mp`` with its block-sparse projections
        packed."""
        mp = dict(mp)
        for proj in _FFN_PROJS:
            if proj not in mp:
                continue
            w = _get_w(mp[proj])
            if not _divisible(w.shape, tile) or not _is_sparse(w, stacked):
                continue
            data = _export_one(w, f"{scope}/{proj}", stacked, proj)
            if data is not None:
                mp[proj] = _param_entry(data, mp[proj]["w"].dtype)
        return mp

    def export_ffn(layer_params, scope, stacked):
        # a dense MLP ({'wi': {'w': ...}, ...}) or an MoE layer's shared
        # experts; routed experts keep raw (E, d, f) arrays, served by the
        # grouped expert matmul
        fp = layer_params.get("ffn")
        if fp is None:
            return layer_params
        if isinstance(fp.get("wi"), dict):
            fp = export_mlp(fp, scope, stacked)
        elif "shared" in fp:
            fp = dict(fp, shared=export_mlp(fp["shared"], f"{scope}/shared",
                                            stacked))
        else:
            return layer_params
        out = dict(layer_params)
        out["ffn"] = fp
        return out

    def export_layer(lp, scope, stacked):
        lp = export_attn(lp, f"{scope}/attn", stacked)
        if include_ffn:
            lp = export_ffn(lp, f"{scope}/ffn", stacked)
        return lp

    new["prefix"] = tuple(export_layer(lp, f"prefix/{i}", False)
                          for i, lp in enumerate(params["prefix"]))
    new["blocks"] = tuple(export_layer(lp, f"blocks/{i}", True)
                          for i, lp in enumerate(params["blocks"]))
    new["suffix"] = tuple(export_layer(lp, f"suffix/{i}", False)
                          for i, lp in enumerate(params["suffix"]))
    if n_shards > 1:
        stats["__sharding__"] = {"n_shards": n_shards,
                                 "per_shard": shard_stats}
    return new, packs, stats


def export_bert_sparse(params, cfg: ModelConfig, tile=(64, 64),
                       include_ffn=True, *, fuse_qkv: bool = True,
                       cross_layer_union: bool = False,
                       use_plans: bool = True,
                       registry: Optional[PatternRegistry] = None,
                       stats_out: Optional[Dict] = None,
                       backend_chooser=None, n_shards: int = 1,
                       pack_quant: str = "none"):
    """BSR export for the (unrolled) BERT encoder.

    Default: one pattern per layer and projection group (fused QKV). With
    ``cross_layer_union=True`` each projection group is union-packed ACROSS
    the encoder layers, so all L layers share one specialization driven by
    per-layer data -- the 12->1 compilation collapse of the paper's task
    buffer; pass a ``registry`` to read the hit/miss instrumentation
    (L-1 hits per group when the union is active).

    ``stats_out``, if given, is filled with the per-group union stats
    (``union_nnzt`` / ``mean_layer_nnzt`` / ``union_overhead``, keyed by
    '<group>/<name>') -- the union-waste instrumentation the paper proposes
    as follow-up. (Kept out of the return value for caller compatibility.)
    """
    layers = params["layers"]
    n_layers = len(layers)
    packs: Dict[str, object] = {}
    shard_stats: Dict[int, Dict] = {}
    attn_new = [dict(lp["attn"]) for lp in layers]
    ffn_new = [dict(lp["ffn"]) for lp in layers]

    # (group, exported name, per-layer weight extractor, source param name)
    specs = []
    fused_ws = [_fused_qkv_weight(lp["attn"], tile, False) for lp in layers] \
        if fuse_qkv else []
    fuse_now = fuse_qkv and all(w is not None for w in fused_ws)
    if fuse_now:
        by_id = {id(lp): w for lp, w in zip(layers, fused_ws)}
        specs.append(("attn", "wqkv", lambda lp: by_id[id(lp)], "wq"))
    else:
        specs += [("attn", proj, (lambda lp, _p=proj: _get_w(lp["attn"][_p])),
                   proj) for proj in _QKV]
    specs.append(("attn", "wo", lambda lp: _get_w(lp["attn"]["wo"]), "wo"))
    if include_ffn:
        specs += [("ffn", proj, (lambda lp, _p=proj: _get_w(lp["ffn"][_p])),
                   proj) for proj in ("wi", "wo")]

    for group, name, getw, src in specs:
        tgt = attn_new if group == "attn" else ffn_new
        dtypes = [lp[group][src]["w"].dtype for lp in layers]
        shard = (n_shards, shard_axis_for(name)) if n_shards > 1 else None
        if cross_layer_union:
            stacked = np.stack([getw(lp) for lp in layers])
            pack, data, union_st = pack_stacked(stacked, tile)
            shard_eff = _effective_shard(pack, shard)
            if backend_chooser is not None:
                choice = _choose(backend_chooser, pack, shard_eff)
                union_st = dict(union_st)
                union_st["autotune"] = {"backend": choice.backend,
                                        "cache_hit": choice.cache_hit,
                                        "mode": choice.mode}
                pk, vals = _realize_backend(pack, data, choice.backend,
                                            registry, shard_eff, shard_stats,
                                            pack_quant)
                qmeta = _quant_meta(pk, vals, data)
                if qmeta:
                    union_st["quant"] = qmeta
                shared = [pk] * n_layers
            elif use_plans and pack_quant != "none":
                pk, vals = _realize_backend(pack, data, "plan", registry,
                                            shard_eff, shard_stats,
                                            pack_quant)
                qmeta = _quant_meta(pk, vals, data)
                if qmeta:
                    union_st = dict(union_st, quant=qmeta)
                shared = [pk] * n_layers
            elif use_plans:
                # one lookup per layer: the registry's hit counters (global
                # AND per-shard) then show the (L-1)-fold reuse of the
                # single unioned specialization
                if shard_eff is not None:
                    shared = [build_sharded_plan(pack, *shard_eff,
                                                 registry=registry,
                                                 shard_stats=shard_stats)
                              for _ in range(n_layers)]
                else:
                    shared = [plan_for_pack(pack, registry)
                              for _ in range(n_layers)]
                vals = pack_plan_data(shared[0], data)
            else:
                shared = [pack] * n_layers
                vals = data
            if stats_out is not None:
                stats_out[f"{group}/{name}"] = union_st
            if shared[0] is None:       # measured dense: weights untouched
                continue
            for i in range(n_layers):
                packs[f"layers/{i}/{group}/{name}"] = shared[i]
                tgt[i][name] = _param_entry_layer(vals, i, dtypes[i])
        else:
            for i, lp in enumerate(layers):
                pk, vals, meta = _serving_pack(getw(lp), tile, use_plans,
                                               registry, backend_chooser,
                                               shard, shard_stats,
                                               pack_quant)
                if stats_out is not None and meta:
                    st = {"union_nnzt": _pack_nnzt(pk)}
                    qmeta = meta.pop("quant", None)
                    if qmeta:
                        st["quant"] = qmeta
                    if meta:
                        st["autotune"] = meta
                    stats_out[f"layers/{i}/{group}/{name}"] = st
                if pk is None:          # measured dense: weight untouched
                    continue
                packs[f"layers/{i}/{group}/{name}"] = pk
                tgt[i][name] = _param_entry(vals, dtypes[i])

    if fuse_now:
        # only drop the per-projection weights of layers whose fused pack
        # was actually exported (an autotune 'dense' verdict keeps them)
        for i, ap in enumerate(attn_new):
            if f"layers/{i}/attn/wqkv" in packs:
                for proj in _QKV:
                    del ap[proj]

    new_layers = []
    for i, lp in enumerate(layers):
        nlp = dict(lp)
        nlp["attn"] = attn_new[i]
        if include_ffn:
            nlp["ffn"] = ffn_new[i]
        new_layers.append(nlp)
    if n_shards > 1 and stats_out is not None:
        stats_out["__sharding__"] = {"n_shards": n_shards,
                                     "per_shard": shard_stats}
    new = dict(params)
    new["layers"] = tuple(new_layers)
    return new, packs


# --------------------------------------------------------------------------
# the family dispatch consumed by repro.serving.prepare_servable
# --------------------------------------------------------------------------

def export_params(params, cfg: ModelConfig, tile=(128, 128), *,
                  fuse_qkv: bool = True, cross_layer_union: bool = True,
                  include_ffn: bool = True, use_plans: bool = True,
                  registry: Optional[PatternRegistry] = None,
                  backend_chooser=None, n_shards: int = 1,
                  pack_quant: str = "none"):
    """Export any model family's param tree to serving form.

    Returns ``(sparse_params, packs, stats)``. Dispatch mirrors
    ``models/api.py``:

      * ``bert``           -> :func:`export_bert_sparse` (cross-layer union
        applies to the unrolled encoder);
      * lm-like families (``dense``/``moe``/``ssm``/``hybrid``/``vlm``)
        -> :func:`export_lm_sparse` (scan-stacked groups are always
        union-packed; ``cross_layer_union`` is implicit);
      * ``audio``          -> no export (the enc-dec forward takes no
        ``packs``); the model serves dense and ``stats`` records the gap.

    ``n_shards`` (the mesh "model" axis size) selects tensor-parallel
    sharded export; see :func:`export_lm_sparse`.
    """
    if cfg.family == "bert":
        stats: Dict[str, Dict] = {}
        sparse_params, packs = export_bert_sparse(
            params, cfg, tile=tile, include_ffn=include_ffn,
            fuse_qkv=fuse_qkv, cross_layer_union=cross_layer_union,
            use_plans=use_plans, registry=registry, stats_out=stats,
            backend_chooser=backend_chooser, n_shards=n_shards,
            pack_quant=pack_quant)
        return sparse_params, packs, stats
    if cfg.family in LM_FAMILIES:
        return export_lm_sparse(params, cfg, tile=tile, fuse_qkv=fuse_qkv,
                                use_plans=use_plans, include_ffn=include_ffn,
                                registry=registry,
                                backend_chooser=backend_chooser,
                                n_shards=n_shards, pack_quant=pack_quant)
    if cfg.family == "audio":
        return params, {}, {"__unsupported__": {
            "family": cfg.family,
            "reason": "enc-dec forward has no packs route; serving dense"}}
    raise ValueError(f"unknown model family {cfg.family!r}")
