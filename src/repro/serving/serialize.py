"""(De)serialization helpers for Servable artifacts.

Three JSON/npz-safe codecs used by ``Servable.save`` / ``load_servable``
(serving/servable.py), layered on top of ``checkpoint/store.py``:

  * **tree spec** -- a JSON description of a param pytree's structure with
    per-leaf dtypes, so a ``like`` tree can be rebuilt at load time and
    handed to ``CheckpointStore.restore`` (which only needs structure +
    dtype, not values);
  * **pack codec** -- RowPackPlan / KernelBSR static patterns flattened into
    npz arrays + JSON meta, deduplicated by pattern fingerprint so the
    cross-layer-union sharing (12 layer scopes -> 1 plan object) survives a
    round-trip and the loaded servable keeps one specialization per group;
  * **config codec** -- ModelConfig (with nested LayerKind, SparsityConfig
    and YarnRope) to plain dicts and back.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

from repro.configs.base import LayerKind, ModelConfig, YarnRope
from repro.core.pattern_reuse import PatternRegistry
from repro.core.sparsity import SparsityConfig
from repro.kernels.autotune import BackendChoice, MaskedPack
from repro.kernels.bsr_matmul import KernelBSR
from repro.kernels.exec_plan import (PlanChoice, QuantPlan, RowPackPlan,
                                     ShardedPlan,
                                     kernel_pattern_fingerprint)

_PLAN_FIELDS = ("col_idx", "slot_mask", "row_of_vrow", "vrow", "slot")
_BSR_FIELDS = ("row_id", "col_id", "t_perm")


class ServableLoadError(RuntimeError):
    """A Servable artifact failed to load: missing, truncated or corrupt
    metadata / pack archive. The message names the offending piece (the
    archive member = "leaf" when one is identifiable), so a bad artifact
    reads as "leaf 'p0_col_idx' is unreadable", not a zlib traceback."""


class LeafReader:
    """Mapping shim over an ``np.load`` NpzFile that converts per-member
    failures into :class:`ServableLoadError` naming the offending leaf.

    npz members decompress lazily, so a truncated or bit-flipped
    ``packs.npz`` loads fine and only fails when a specific member is
    read -- deep inside the pack codec. Routing every read through this
    shim pins the error to the artifact and leaf instead."""

    def __init__(self, npz, path: str):
        self._npz = npz
        self._path = path

    def __getitem__(self, name: str) -> np.ndarray:
        try:
            return self._npz[name]
        except KeyError:
            raise ServableLoadError(
                f"pack archive {self._path} is missing leaf {name!r} "
                f"(truncated or incompatible artifact)") from None
        except Exception as e:  # zlib.error / BadZipFile / ValueError ...
            raise ServableLoadError(
                f"pack archive {self._path}: leaf {name!r} is unreadable "
                f"({type(e).__name__}: {e})") from e

    def __contains__(self, name: str) -> bool:
        return name in self._npz


def pattern_key(pack) -> bytes:
    """Fingerprint of a static pattern, uniform across the pack kinds
    (plan / bsr / autotuned choice / masked) -- the dedupe key here and the
    uniqueness key of ``Servable.stats()``. Choice/masked packs embed the
    backend in their fingerprint, so the same pattern pinned to two
    different backends is (correctly) two keys."""
    if isinstance(pack, (RowPackPlan, PlanChoice, QuantPlan, BackendChoice,
                         MaskedPack)):
        return pack.fingerprint
    return kernel_pattern_fingerprint(pack)


# --------------------------------------------------------------------------
# tree spec
# --------------------------------------------------------------------------

def tree_spec(tree) -> dict:
    """JSON-safe structure descriptor of a pytree of arrays (dict / tuple /
    list containers, array or None leaves)."""
    if isinstance(tree, dict):
        return {"kind": "dict",
                "items": {str(k): tree_spec(v) for k, v in tree.items()}}
    if isinstance(tree, (tuple, list)):
        return {"kind": "tuple" if isinstance(tree, tuple) else "list",
                "items": [tree_spec(v) for v in tree]}
    if tree is None:
        return {"kind": "none"}
    return {"kind": "leaf", "dtype": str(jnp.asarray(tree).dtype)}


def build_like(spec: dict):
    """Rebuild a placeholder tree from :func:`tree_spec` output -- same
    structure, scalar zero leaves carrying the recorded dtype (all
    ``CheckpointStore.restore`` consults)."""
    kind = spec["kind"]
    if kind == "dict":
        return {k: build_like(v) for k, v in spec["items"].items()}
    if kind in ("tuple", "list"):
        items = [build_like(v) for v in spec["items"]]
        return tuple(items) if kind == "tuple" else items
    if kind == "none":
        return None
    return np.zeros((), np.dtype(spec["dtype"]))


# --------------------------------------------------------------------------
# pack codec
# --------------------------------------------------------------------------

def packs_to_arrays(packs: Dict[str, object]) -> Tuple[dict, dict]:
    """-> (npz arrays, JSON meta). Unique patterns stored once (fingerprint
    dedupe); ``meta['keys']`` fans each layer scope back out to its ref."""
    arrays: Dict[str, np.ndarray] = {}
    metas: List[dict] = []
    index_of: Dict[bytes, int] = {}
    keys = []
    for key, pk in packs.items():
        fp = pattern_key(pk)
        idx = index_of.get(fp)
        if idx is None:
            idx = len(metas)
            index_of[fp] = idx
            arrays[f"p{idx}_fingerprint"] = np.frombuffer(fp, np.uint8)
            if isinstance(pk, QuantPlan):
                # quantized wrapper: quant meta + the inner plan's fields
                # and fingerprint (registry-shared with any unquantized
                # packs of the same pattern). ``codec: 1`` versions the
                # quant entry itself; files written before this kind
                # existed simply never contain it, so they load unchanged.
                plan = pk.plan
                m = {"kind": "quant_plan", "codec": 1,
                     "qdtype": pk.qdtype, "granularity": pk.granularity,
                     "backend": pk.backend, "shape": list(plan.shape),
                     "tile": list(plan.tile), "nnzt": plan.nnzt,
                     "real_nnzt": plan.real_nnzt,
                     "sharded": isinstance(plan, ShardedPlan)}
                arrays[f"p{idx}_plan_fingerprint"] = np.frombuffer(
                    plan.fingerprint, np.uint8)
                for f in _PLAN_FIELDS:
                    arrays[f"p{idx}_{f}"] = np.asarray(getattr(plan, f))
                if isinstance(plan, ShardedPlan):
                    m["n_shards"] = plan.n_shards
                    m["shard_axis"] = plan.shard_axis
                    sfps = list(plan.shard_fingerprints)
                    arrays[f"p{idx}_shard_fp_lens"] = np.array(
                        [len(s) for s in sfps], np.int64)
                    arrays[f"p{idx}_shard_fps"] = np.frombuffer(
                        b"".join(sfps), np.uint8)
                metas.append(m)
            elif isinstance(pk, ShardedPlan):
                # shard-partitioned plan: plan fields + shard layout meta +
                # per-shard sub-pattern fingerprints (the registry/autotune
                # keys survive the round-trip; the mesh itself does NOT --
                # load_servable rebuilds it from the spec)
                metas.append({"kind": "sharded_plan",
                              "shape": list(pk.shape),
                              "tile": list(pk.tile), "nnzt": pk.nnzt,
                              "real_nnzt": pk.real_nnzt,
                              "n_shards": pk.n_shards,
                              "shard_axis": pk.shard_axis})
                for f in _PLAN_FIELDS:
                    arrays[f"p{idx}_{f}"] = np.asarray(getattr(pk, f))
                sfps = list(pk.shard_fingerprints)
                arrays[f"p{idx}_shard_fp_lens"] = np.array(
                    [len(s) for s in sfps], np.int64)
                arrays[f"p{idx}_shard_fps"] = np.frombuffer(
                    b"".join(sfps), np.uint8)
            elif isinstance(pk, RowPackPlan):
                metas.append({"kind": "plan", "shape": list(pk.shape),
                              "tile": list(pk.tile), "nnzt": pk.nnzt,
                              "real_nnzt": pk.real_nnzt})
                for f in _PLAN_FIELDS:
                    arrays[f"p{idx}_{f}"] = np.asarray(getattr(pk, f))
            elif isinstance(pk, PlanChoice):
                # plan fields + the pinned backend; the inner plan's own
                # fingerprint is stored too so the registry-cached rebuild
                # shares the plan with any bare-'plan' packs of the same
                # pattern
                plan = pk.plan
                metas.append({"kind": "plan_choice", "backend": pk.backend,
                              "shape": list(plan.shape),
                              "tile": list(plan.tile), "nnzt": plan.nnzt,
                              "real_nnzt": plan.real_nnzt})
                arrays[f"p{idx}_plan_fingerprint"] = np.frombuffer(
                    plan.fingerprint, np.uint8)
                for f in _PLAN_FIELDS:
                    arrays[f"p{idx}_{f}"] = np.asarray(getattr(plan, f))
            elif isinstance(pk, MaskedPack):
                metas.append({"kind": "masked", "shape": list(pk.shape),
                              "tile": list(pk.tile)})
                arrays[f"p{idx}_tile_mask"] = np.asarray(pk.tile_mask, bool)
            elif isinstance(pk, BackendChoice):
                inner = pk.pack
                metas.append({"kind": "choice", "backend": pk.backend,
                              "shape": list(inner.shape),
                              "tile": list(inner.tile),
                              "real_nnzt": inner.real_nnzt})
                for f in _BSR_FIELDS:
                    arrays[f"p{idx}_{f}"] = np.asarray(getattr(inner, f))
            else:
                # structural fields only: serving rebuilds KernelBSR around
                # the values held in the params tree (models/common.linear),
                # so pk.data is never read back -- storing it would duplicate
                # every packed weight in the artifact
                metas.append({"kind": "bsr", "shape": list(pk.shape),
                              "tile": list(pk.tile),
                              "real_nnzt": pk.real_nnzt})
                for f in _BSR_FIELDS:
                    arrays[f"p{idx}_{f}"] = np.asarray(getattr(pk, f))
        keys.append({"key": key, "ref": idx})
    return arrays, {"patterns": metas, "keys": keys}


def packs_from_arrays(meta: dict, arrays, registry: PatternRegistry = None
                      ) -> Dict[str, object]:
    """Inverse of :func:`packs_to_arrays`. Plans are rebuilt through the
    registry's fingerprint-keyed cache so the loaded servable shares one
    object (and downstream one jit specialization) per unique pattern."""
    built = []
    for idx, m in enumerate(meta["patterns"]):
        fp = bytes(np.asarray(arrays[f"p{idx}_fingerprint"], np.uint8))
        if m["kind"] == "quant_plan":
            plan_fp = bytes(np.asarray(arrays[f"p{idx}_plan_fingerprint"],
                                       np.uint8))

            def build_inner(idx=idx, m=m, plan_fp=plan_fp):
                fields = dict(
                    col_idx=np.asarray(arrays[f"p{idx}_col_idx"], np.int32),
                    slot_mask=np.asarray(arrays[f"p{idx}_slot_mask"], bool),
                    row_of_vrow=np.asarray(arrays[f"p{idx}_row_of_vrow"],
                                           np.int32),
                    vrow=np.asarray(arrays[f"p{idx}_vrow"], np.int32),
                    slot=np.asarray(arrays[f"p{idx}_slot"], np.int32),
                    shape=tuple(m["shape"]), tile=tuple(m["tile"]),
                    nnzt=int(m["nnzt"]), real_nnzt=int(m["real_nnzt"]),
                    fingerprint=plan_fp)
                if not m.get("sharded"):
                    return RowPackPlan(**fields)
                lens = np.asarray(arrays[f"p{idx}_shard_fp_lens"], np.int64)
                blob = bytes(np.asarray(arrays[f"p{idx}_shard_fps"],
                                        np.uint8))
                offs = np.concatenate([[0], np.cumsum(lens)])
                sfps = tuple(blob[offs[i]: offs[i + 1]]
                             for i in range(len(lens)))
                return ShardedPlan(**fields, n_shards=int(m["n_shards"]),
                                   shard_axis=m["shard_axis"],
                                   shard_fingerprints=sfps)
            cache_key = (("sharded_plan_codec", plan_fp) if m.get("sharded")
                         else ("rowpack_plan", plan_fp))
            plan = (registry.cached(cache_key, build_inner)
                    if registry is not None else build_inner())
            built.append(QuantPlan(plan, qdtype=m["qdtype"],
                                   granularity=m["granularity"],
                                   backend=m["backend"]))
        elif m["kind"] == "sharded_plan":
            def build_sharded(idx=idx, m=m, fp=fp):
                lens = np.asarray(arrays[f"p{idx}_shard_fp_lens"], np.int64)
                blob = bytes(np.asarray(arrays[f"p{idx}_shard_fps"],
                                        np.uint8))
                offs = np.concatenate([[0], np.cumsum(lens)])
                sfps = tuple(blob[offs[i]: offs[i + 1]]
                             for i in range(len(lens)))
                return ShardedPlan(
                    col_idx=np.asarray(arrays[f"p{idx}_col_idx"], np.int32),
                    slot_mask=np.asarray(arrays[f"p{idx}_slot_mask"], bool),
                    row_of_vrow=np.asarray(arrays[f"p{idx}_row_of_vrow"],
                                           np.int32),
                    vrow=np.asarray(arrays[f"p{idx}_vrow"], np.int32),
                    slot=np.asarray(arrays[f"p{idx}_slot"], np.int32),
                    shape=tuple(m["shape"]), tile=tuple(m["tile"]),
                    nnzt=int(m["nnzt"]), real_nnzt=int(m["real_nnzt"]),
                    fingerprint=fp, n_shards=int(m["n_shards"]),
                    shard_axis=m["shard_axis"], shard_fingerprints=sfps)
            if registry is not None:
                built.append(registry.cached(("sharded_plan_codec", fp),
                                             build_sharded))
            else:
                built.append(build_sharded())
        elif m["kind"] == "plan":
            def build(idx=idx, m=m, fp=fp):
                return RowPackPlan(
                    col_idx=np.asarray(arrays[f"p{idx}_col_idx"], np.int32),
                    slot_mask=np.asarray(arrays[f"p{idx}_slot_mask"], bool),
                    row_of_vrow=np.asarray(arrays[f"p{idx}_row_of_vrow"],
                                           np.int32),
                    vrow=np.asarray(arrays[f"p{idx}_vrow"], np.int32),
                    slot=np.asarray(arrays[f"p{idx}_slot"], np.int32),
                    shape=tuple(m["shape"]), tile=tuple(m["tile"]),
                    nnzt=int(m["nnzt"]), real_nnzt=int(m["real_nnzt"]),
                    fingerprint=fp)
            if registry is not None:
                built.append(registry.cached(("rowpack_plan", fp), build))
            else:
                built.append(build())
        elif m["kind"] == "plan_choice":
            plan_fp = bytes(np.asarray(arrays[f"p{idx}_plan_fingerprint"],
                                       np.uint8))
            def build_plan_obj(idx=idx, m=m, plan_fp=plan_fp):
                return RowPackPlan(
                    col_idx=np.asarray(arrays[f"p{idx}_col_idx"], np.int32),
                    slot_mask=np.asarray(arrays[f"p{idx}_slot_mask"], bool),
                    row_of_vrow=np.asarray(arrays[f"p{idx}_row_of_vrow"],
                                           np.int32),
                    vrow=np.asarray(arrays[f"p{idx}_vrow"], np.int32),
                    slot=np.asarray(arrays[f"p{idx}_slot"], np.int32),
                    shape=tuple(m["shape"]), tile=tuple(m["tile"]),
                    nnzt=int(m["nnzt"]), real_nnzt=int(m["real_nnzt"]),
                    fingerprint=plan_fp)
            if registry is not None:
                plan = registry.cached(("rowpack_plan", plan_fp),
                                       build_plan_obj)
            else:
                plan = build_plan_obj()
            built.append(PlanChoice(plan, m["backend"]))
        elif m["kind"] == "masked":
            built.append(MaskedPack(
                tile_mask=np.asarray(arrays[f"p{idx}_tile_mask"], bool),
                shape=tuple(m["shape"]), tile=tuple(m["tile"])))
        else:
            col_id = np.asarray(arrays[f"p{idx}_col_id"], np.int32)
            bn, bk = (int(t) for t in m["tile"])
            bsr = KernelBSR(
                # zeros placeholder: serve-time data comes from the params
                # tree, never from the pack (models/common.linear)
                data=jnp.zeros((len(col_id), bn, bk), jnp.float32),
                row_id=np.asarray(arrays[f"p{idx}_row_id"], np.int32),
                col_id=col_id,
                t_perm=np.asarray(arrays[f"p{idx}_t_perm"], np.int32),
                real_nnzt=int(m["real_nnzt"]), shape=tuple(m["shape"]),
                tile=(bn, bk))
            built.append(BackendChoice(bsr, m["backend"])
                         if m["kind"] == "choice" else bsr)
    return {e["key"]: built[e["ref"]] for e in meta["keys"]}


# --------------------------------------------------------------------------
# config codec
# --------------------------------------------------------------------------

def config_to_dict(cfg: ModelConfig) -> dict:
    return dataclasses.asdict(cfg)


def config_from_dict(d: dict) -> ModelConfig:
    d = dict(d)
    d["pattern"] = tuple(LayerKind(**k) for k in d.get("pattern", ()))
    d["prefix"] = tuple(LayerKind(**k) for k in d.get("prefix", ()))
    if d.get("sparsity"):
        sp = dict(d["sparsity"])
        sp["block_shape"] = tuple(sp["block_shape"])
        sp["targets"] = tuple(sp["targets"])
        d["sparsity"] = SparsityConfig(**sp)
    if d.get("rope_yarn"):
        d["rope_yarn"] = YarnRope(**d["rope_yarn"])
    return ModelConfig(**d)
