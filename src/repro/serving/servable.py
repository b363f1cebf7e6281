"""prepare_servable / Servable / load_servable: the serving facade.

One spec-driven entry point owns the whole algorithm->compilation pipeline
the paper argues must be co-designed:

    prune (core.pruner recipe)            -- algorithm side
    -> BSR export (serving.export)        -- layout
    -> RowPackPlan construction           -- execution schedule
    -> PatternRegistry caching            -- cross-layer/task reuse

for every model family, dispatched through ``models/api.py``. The returned
:class:`Servable` is a self-contained handle: ``forward`` / ``decode_step``
serve through the packed weights, ``stats`` surfaces the co-design
instrumentation (density, union overhead, registry hits, padded-FLOP
ratio), and ``save`` / :func:`load_servable` persist the artifact through
``checkpoint/store.py`` so export cost is paid once per model, not once per
process.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import CheckpointStore
from repro.configs.base import ModelConfig
from repro.models.sampling import sample_tokens
from repro.core.pattern_reuse import PatternRegistry
from repro.core.pruner import _path_name, oneshot_prune, tied_prune
from repro.kernels.exec_plan import QuantPlan, RowPackPlan, ShardedPlan
from repro.kernels.flash_decode import decode_kernel_override
from repro.models import api as model_api
from repro.runtime.trace import span
from repro.serving.export import export_params
from repro.serving.serialize import (LeafReader, ServableLoadError,
                                     build_like, config_from_dict,
                                     config_to_dict, packs_from_arrays,
                                     packs_to_arrays, pattern_key, tree_spec)
from repro.serving.spec import ServingSpec

#: the single checkpoint slot a Servable occupies in its store directory
SERVABLE_STEP = 0
_PACKS_FILE = "packs.npz"


def _norm_path(name: str) -> str:
    """'layers/[0]/attn/wqkv/w' (tree-path rendering, core.pruner._path_name)
    -> 'layers/0/attn/wqkv/w' (the pack-key convention)."""
    return "/".join(tok.strip("[]") for tok in name.split("/"))


def _cast_packed(params, packs, jdtype):
    """Cast only the packed projection values to the spec dtype (embeddings,
    norms, heads keep the model dtype). Quantized packs are exempt: their
    int8/fp8 values and fp32 scales ARE the storage format -- casting either
    to the model dtype would silently dequantize or lose scale precision."""
    targets = {key + "/w" for key, pk in packs.items()
               if not isinstance(pk, QuantPlan)}

    def one(path, leaf):
        name = _norm_path(_path_name(path))
        return leaf.astype(jdtype) if name in targets else leaf
    return jax.tree_util.tree_map_with_path(one, params)


# --------------------------------------------------------------------------
# mesh placement (spec.mesh_shape: the tensor/data-parallel serving path)
# --------------------------------------------------------------------------

def make_serving_mesh(spec) -> "jax.sharding.Mesh":
    """Build the ``("data", "model")`` mesh a spec asks for, with an
    actionable error when the process doesn't expose enough devices
    (host-platform runs need ``XLA_FLAGS=--xla_force_host_platform_
    device_count=N`` set before jax initializes)."""
    from repro.launch.mesh import make_mesh
    need = spec.data_shards * spec.model_shards
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"spec.mesh_shape={tuple(spec.mesh_shape)} needs {need} devices "
            f"but only {have} are visible; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before importing"
            f" jax")
    return make_mesh(tuple(spec.mesh_shape), ("data", "model"))


def attach_mesh(packs, mesh):
    """Attach ``mesh`` to every ShardedPlan pack (static metadata consumed
    by the models/common.linear sharding hook), including ShardedPlans
    wrapped in a QuantPlan. Identical patterns keep sharing one underlying
    layout -- with_mesh is a shallow replace."""
    out, seen = {}, {}
    for key, pk in packs.items():
        inner = pk.plan if isinstance(pk, QuantPlan) else pk
        if isinstance(inner, ShardedPlan) and inner.mesh is not mesh:
            if id(pk) not in seen:
                seen[id(pk)] = pk.with_mesh(mesh)
            pk = seen[id(pk)]
        out[key] = pk
    return out


def serving_param_shardings(params, packs, mesh):
    """NamedSharding tree for a serving param tree:

      * ShardedPlan-packed values ``(..., V, P, bn, bk)`` shard their vrow
        axis over "model" -- shard ``s`` of the plan lands on device column
        ``s``, per-device pack bytes drop ~n_shards-fold;
      * unsharded pack values replicate (their pattern did not divide);
      * every dense leaf follows ``launch/sharding.spec_for_param`` in
        inference mode (TP-only: no per-layer weight all-gathers).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.sharding import spec_for_param
    packed = {}
    for key, pk in packs.items():
        packed[key + "/w"] = pk
        if isinstance(pk, QuantPlan):
            packed[key + "/scale"] = pk

    def one(path, leaf):
        name = _norm_path(_path_name(path))
        pk = packed.get(name)
        inner = pk.plan if isinstance(pk, QuantPlan) else pk
        if isinstance(inner, ShardedPlan):
            spec = [None] * leaf.ndim
            # qvalues (..., V, P, bn, bk) and scales (..., V, P|1) both
            # shard their vrow axis over "model"
            vrow_axis = leaf.ndim - (2 if name.endswith("/scale") else 4)
            spec[vrow_axis] = "model"
            return NamedSharding(mesh, P(*spec))
        if pk is not None:                      # packed but not shardable
            return NamedSharding(mesh, P())
        return NamedSharding(
            mesh, spec_for_param(name, leaf.shape, mesh, mode="inference"))
    return jax.tree_util.tree_map_with_path(one, params)


class Servable:
    """Handle over (packed params, static patterns, config, spec).

    Not constructed directly -- use :func:`prepare_servable` or
    :func:`load_servable`.
    """

    def __init__(self, params, cfg: ModelConfig, spec: ServingSpec,
                 packs: Dict[str, object], registry: PatternRegistry,
                 export_stats: Optional[Dict] = None,
                 stats_at_save: Optional[Dict] = None, mesh=None):
        self.params = params
        self.cfg = cfg
        self.spec = spec
        self.packs = packs
        self.registry = registry
        self.mesh = mesh                 # jax.sharding.Mesh | None
        self.export_stats = export_stats or {}
        self.stats_at_save = stats_at_save
        self._fwd_fn = None
        self._decode_kind = None
        self._decode_fn = None
        self._decode_many_fn = None
        self._engine_decode = None
        self._engine_decode_many = None
        self._engine_prefill = None
        self._engine_write = None
        self._engine_free = None
        self._engine_paged = None
        self._engine_suffix = None
        self._mesh_paged_fns: Dict[Any, tuple] = {}
        self._mesh_suffix_fns: Dict[Any, Any] = {}
        # mesh engines: (decode, decode_many, write, free) jits cached per
        # cache-sharding tree, so engines over the same placement share
        # executables exactly like the unsharded path
        self._mesh_engine_fns: Dict[Any, tuple] = {}

    # -- serving ----------------------------------------------------------
    def _as_batch(self, batch) -> Dict[str, Any]:
        if isinstance(batch, dict):
            return batch
        return {"tokens": jnp.asarray(batch)}

    def forward(self, batch):
        """batch dict (models/api.py schema) or raw tokens -> logits f32.

        The callable is jit'd once per Servable with the packs held static;
        identical patterns across layers share one specialization (plans
        hash by pattern fingerprint). The span ``repro.servable.forward``
        covers the dispatch only: the caller's wait for the logits stays
        outside it."""
        if self._fwd_fn is None:
            cfg, packs = self.cfg, self.packs
            self._fwd_fn = jax.jit(
                lambda p, b: model_api.model_forward(p, cfg, b, packs=packs))
        with span("repro.servable.forward"):
            logits, _aux = self._fwd_fn(self.params, self._as_batch(batch))
        return logits

    def init_cache(self, batch_size: int, cache_len: int, frames=None,
                   paged=None):
        cache = model_api.init_cache(self.params, self.cfg, batch_size,
                                     cache_len, frames=frames, paged=paged)
        if self.mesh is not None:
            # slots over "data", heads/state over "model"; lifecycle ops
            # stay sharding-preserving device scatters from here on
            cache = model_api.shard_cache(cache, self.cfg, self.mesh)
        return cache

    def decode_kernel_kind(self) -> str:
        """Resolve the attention decode kernel every jitted decode closure
        of this servable pins at trace time ('xla' | 'flash' | 'auto'):
        the ``REPRO_DECODE_KERNEL`` env var wins when set to a non-'auto'
        value, then a non-'auto' ``spec.decode_kernel``, then -- for
        'auto' -- :func:`repro.kernels.autotune.choose_decode_kernel` over
        this config's decode shape (so the choice is measured/stubbed per
        device and persisted like every other autotune winner)."""
        if self._decode_kind is None:
            env = os.environ.get("REPRO_DECODE_KERNEL", "").strip()
            if env and env != "auto":
                self._decode_kind = env
            elif self.spec.decode_kernel != "auto":
                self._decode_kind = self.spec.decode_kernel
            else:
                cfg = self.cfg
                if not getattr(cfg, "n_kv_heads", 0):
                    # attention-free families (pure SSM) never reach the
                    # decode-attention kernel -- nothing to tune
                    self._decode_kind = "xla"
                else:
                    from repro.kernels.autotune import choose_decode_kernel
                    self._decode_kind = choose_decode_kernel(
                        b=8, t=512, hq=cfg.n_heads, hkv=cfg.n_kv_heads,
                        d=cfg.head_dim).backend
        return self._decode_kind

    def decode_step(self, cache, token, pos):
        """(cache, token (B,1), pos) -> (logits, new_cache); encoder-only
        families raise (models/api.py contract). ``pos`` is a scalar or a
        ragged int32 (B,) vector of per-slot positions (-1 = inactive row,
        cache untouched) -- the continuous-batching calling convention."""
        if self._decode_fn is None:
            cfg, packs = self.cfg, self.packs
            kind = self.decode_kernel_kind()

            def step(p, c, t, s):
                with decode_kernel_override(kind):
                    return model_api.decode_step(p, c, cfg, t, s,
                                                 packs=packs)
            self._decode_fn = jax.jit(step)
        return self._decode_fn(self.params, cache, token, pos)

    def decode_many(self, cache, token, pos, n_steps, *, remaining=None,
                    eos_id=None, key=None, temperature: float = 0.0,
                    top_k: int = 0):
        """Fused K-step decode (``models.api.decode_many``): K decode steps,
        sampling and per-slot EOS/stop masking inside ONE jitted
        ``lax.scan`` -- one host round-trip per window instead of per
        token. Returns ``(tokens (K, B), valid (K, B), state)``; this is
        the non-donating API (the engine hot loop uses the donated
        executable, ``_engine_decode_many_fn``). Retraces per distinct
        ``(K, temperature, top_k)``."""
        if self._decode_many_fn is None:
            cfg, packs = self.cfg, self.packs
            kind = self.decode_kernel_kind()

            def fused(p, c, t, s, rem, eos, k, n, temp, tk):
                with decode_kernel_override(kind):
                    return model_api.decode_many(
                        p, c, cfg, t, s, n, packs=packs, remaining=rem,
                        eos_id=eos, key=k, temperature=temp, top_k=tk)

            self._decode_many_fn = jax.jit(fused, static_argnums=(7, 8, 9))
        b = jnp.shape(token)[0]
        if remaining is None:
            remaining = jnp.full((b,), jnp.iinfo(jnp.int32).max // 2,
                                 jnp.int32)
        if eos_id is None:
            eos_id = jnp.full((b,), -1, jnp.int32)
        else:
            eos_id = jnp.broadcast_to(jnp.asarray(eos_id, jnp.int32), (b,))
        if key is None:
            key = jax.random.PRNGKey(0)
        return self._decode_many_fn(self.params, cache, token, pos,
                                    jnp.asarray(remaining, jnp.int32),
                                    eos_id, key, int(n_steps),
                                    float(temperature), int(top_k))

    def engine(self, max_slots: int = 8, cache_len: int = 256, **kw):
        """Construct a continuous-batching :class:`~repro.serving.engine.
        ServingEngine` over this servable: request slots, admission queue,
        bucketed prefill, one batched decode per step (docs/API.md)."""
        from repro.serving.engine import ServingEngine
        return ServingEngine(self, max_slots=max_slots, cache_len=cache_len,
                             **kw)

    def _engine_decode_fn(self, cache_shardings=None):
        """Jitted batched decode shared by every engine of this servable
        (jit retraces per (max_slots, cache) shape and per static
        (temperature, top_k); executables persist across engine
        instances). Returns ``(sampled_tokens (B,), ok (B,) bool, logits,
        cache)`` -- sampling (greedy argmax, or temperature/top-k with the
        slot+position-keyed PRNG of models/sampling.py) runs on device so
        the hot loop only moves B int32s + B bools to host; ``ok`` is the
        per-slot non-finite guard (False = that slot's logits row went
        NaN/inf and the engine must quarantine it), and the full logits
        land on host only when an engine collects them. The cache argument
        is DONATED -- engine hot-loop use only; :meth:`decode_step` is the
        non-donating API.

        ``cache_shardings`` (mesh engines) pins the output cache to the
        engine cache's placement, so the donated buffers stay reusable
        step over step instead of XLA re-deciding (and copying) per
        leaf; cached per sharding tree by :meth:`engine_fns`."""
        if self._engine_decode is None or cache_shardings is not None:
            cfg, packs = self.cfg, self.packs
            kind = self.decode_kernel_kind()

            def decode(p, c, t, s, key, temperature, top_k):
                with decode_kernel_override(kind):
                    logits, c = model_api.decode_step(p, c, cfg, t, s,
                                                      packs=packs)
                rows = logits[:, 0, :]
                ok = jnp.isfinite(rows).all(axis=-1)
                nxt = sample_tokens(rows, key, s,
                                    temperature=temperature, top_k=top_k)
                return nxt, ok, logits, c

            kw = {} if cache_shardings is None else \
                {"out_shardings": (None, None, None, cache_shardings)}
            fn = jax.jit(decode, donate_argnums=(1,),
                         static_argnums=(5, 6), **kw)
            if cache_shardings is not None:
                return fn
            self._engine_decode = fn
        return self._engine_decode

    def _engine_decode_many_fn(self, cache_shardings=None):
        """Jitted fused K-step decode for the engine hot loop: K decode
        steps + sampling + per-slot EOS/budget masking inside one
        ``lax.scan`` (``models.api.decode_many``), cache DONATED. One
        executable per static (K, temperature, top_k) -- the engine bounds
        K by ``sync_every``, so the trace count stays small and every
        window after the first reuses a warm executable.
        ``cache_shardings`` as in :meth:`_engine_decode_fn`."""
        if self._engine_decode_many is None or cache_shardings is not None:
            cfg, packs = self.cfg, self.packs
            kind = self.decode_kernel_kind()

            def fused(p, c, t, s, rem, eos, key, n_steps, temperature,
                      top_k):
                with decode_kernel_override(kind):
                    return model_api.decode_many(
                        p, c, cfg, t, s, n_steps, packs=packs,
                        remaining=rem, eos_id=eos, key=key,
                        temperature=temperature, top_k=top_k)

            kw = {}
            if cache_shardings is not None:
                kw["out_shardings"] = (
                    None, None, {"token": None, "pos": None,
                                 "remaining": None, "failed": None,
                                 "cache": cache_shardings})
            fn = jax.jit(fused, donate_argnums=(1,),
                         static_argnums=(7, 8, 9), **kw)
            if cache_shardings is not None:
                return fn
            self._engine_decode_many = fn
        return self._engine_decode_many

    def _engine_prefill_fn(self):
        """Jitted prompt prefill shared by every engine of this servable.
        Uniform signature ``(params, cache1, tokens (bucket,), pos_seq
        (bucket,), length) -> (cache1, logits (bucket, V))``; one trace per
        bucket length serves every admission (``length`` is traced).

        lm-family models run the ONE-PASS forward prefill
        (``models.api.prefill_cache``): the whole prompt streams the weights
        once, instead of once per token. Audio (enc-dec) scans the
        single-token decode path -- its decoder prompts are BOS-sized, and
        padding steps carry pos = -1 so they write nothing."""
        if self._engine_prefill is None:
            cfg, packs = self.cfg, self.packs

            if cfg.family == "audio":
                def prefill(params, cache, tokens, pos_seq, length):
                    def step(c, tp):
                        tok, p = tp
                        logits, c = model_api.decode_step(
                            params, c, cfg, tok[None, None], p[None])
                        return c, logits[0, 0]
                    return jax.lax.scan(step, cache, (tokens, pos_seq))
            else:
                def prefill(params, cache, tokens, pos_seq, length):
                    logits, cache = model_api.prefill_cache(
                        params, cache, cfg, tokens[None], length, packs=packs)
                    return cache, logits[0]

            self._engine_prefill = jax.jit(prefill)
        return self._engine_prefill

    def engine_fns(self, cache_shardings=None):
        """The engine's four cache-carrying jits ``(decode, decode_many,
        write_slot, free_slot)``. Unsharded engines share the
        Servable-cached executables; mesh engines share them per
        cache-sharding tree (NamedSharding is hashable), so constructing
        a second engine over the same placement retraces nothing."""
        if cache_shardings is None:
            return (self._engine_decode_fn(), self._engine_decode_many_fn(),
                    *self._engine_slot_fns())
        leaves, treedef = jax.tree_util.tree_flatten(cache_shardings)
        key = (treedef, tuple(leaves))
        if key not in self._mesh_engine_fns:
            self._mesh_engine_fns[key] = (
                self._engine_decode_fn(cache_shardings),
                self._engine_decode_many_fn(cache_shardings),
                *self._engine_slot_fns(cache_shardings))
        return self._mesh_engine_fns[key]

    def _engine_slot_fns(self, out_shardings=None):
        """Jitted ``(write_slot, free_slot)`` with the batched cache DONATED:
        slot insertion and retirement become in-place scatters instead of
        whole-cache copies (the slot index is traced, so one executable per
        cache shape serves every slot).

        ``out_shardings`` (a NamedSharding tree matching the cache, mesh
        engines only) pins the outputs to the engine cache's placement so
        lifecycle ops never regather it; sharded pairs are cached per
        sharding tree by :meth:`engine_fns`, the unsharded pair directly
        on the Servable."""
        cfg = self.cfg
        kw = {} if out_shardings is None else \
            {"out_shardings": out_shardings}

        def build():
            return (jax.jit(
                        lambda c, i, sub: model_api.write_slot(c, cfg, i,
                                                               sub),
                        donate_argnums=(0,), **kw),
                    jax.jit(lambda c, i: model_api.free_slot(c, cfg, i),
                            donate_argnums=(0,), **kw))
        if out_shardings is not None:
            return build()
        if self._engine_write is None:
            self._engine_write, self._engine_free = build()
        return self._engine_write, self._engine_free

    def suffix_prefill_fn(self, cache_shardings=None):
        """Jitted suffix/chunk prefill over the BATCHED engine cache:
        ``suffix_prefill(params, cache, tokens (S,), slot, start, length)
        -> (cache, logits (S, V))``. One trace per bucketed chunk length S
        serves every chunk (``slot``/``start``/``length`` are traced). The
        cache is DONATED, like the decode and write-slot jits: at serving
        scale the batched cache is tens of MB and an un-donated copy per
        chunk (~35 ms observed at 8x512 slots) would dwarf the chunk's own
        compute. Fault containment is unchanged -- the chaos site
        ``engine.prefill_chunk`` fires BEFORE dispatch, where the buffer
        has not yet been consumed (tests/test_chaos.py).

        Shared by the paged shared-prefix path (PR 7,
        :meth:`paged_engine_fns`) and the dense-KV chunked-prefill
        scheduler (docs/API.md §SLO scheduling) -- the model-layer entry
        point is the same ``models.api.prefill_suffix`` either way.
        Cached like the other engine jits: once on the Servable when
        unsharded, per cache-sharding tree for mesh engines."""
        cfg, packs = self.cfg, self.packs

        def build():
            def suffix(params, cache, tokens, slot, start, length):
                logits, cache = model_api.prefill_suffix(
                    params, cache, cfg, tokens[None], slot, start, length,
                    packs=packs)
                return cache, logits[0]
            skw = {} if cache_shardings is None else \
                {"out_shardings": (cache_shardings, None)}
            return jax.jit(suffix, donate_argnums=(1,), **skw)

        if cache_shardings is None:
            if self._engine_suffix is None:
                self._engine_suffix = build()
            return self._engine_suffix
        leaves, treedef = jax.tree_util.tree_flatten(cache_shardings)
        key = (treedef, tuple(leaves))
        if key not in self._mesh_suffix_fns:
            self._mesh_suffix_fns[key] = build()
        return self._mesh_suffix_fns[key]

    def paged_engine_fns(self, cache_shardings=None):
        """The paged engine's three extra cache-carrying jits
        ``(write_paged, restore_paged, suffix_prefill)``:

        - ``write_paged(cache, slot, sub, page_row)`` -- scatter a dense
          batch-1 prefill result into the slot's pages (cache DONATED, the
          paged analogue of ``write_slot``);
        - ``restore_paged(cache, slot, page_row, resume_len)`` -- re-attach
          retained pages after preemption, NOT donated: restore runs inside
          admission's failure envelope, and a donated cache would be
          invalidated even when the op is abandoned;
        - ``suffix_prefill(params, cache, tokens (S,), slot, start,
          length)`` -- prefill only the uncached prompt suffix against a
          shared resident prefix; :meth:`suffix_prefill_fn`, shared with
          the dense chunked-prefill path. Returns ``(cache, logits
          (S, V))``.

        Cached like :meth:`engine_fns`: unsharded engines share the
        Servable-held trio, mesh engines share per cache-sharding tree."""
        cfg, packs = self.cfg, self.packs
        kw = {} if cache_shardings is None else \
            {"out_shardings": cache_shardings}

        def build():
            write = jax.jit(
                lambda c, i, sub, row: model_api.write_slot_paged(
                    c, cfg, i, sub, row),
                donate_argnums=(0,), **kw)
            restore = jax.jit(
                lambda c, i, row, n: model_api.restore_slot_paged(
                    c, cfg, i, row, n), **kw)
            return write, restore, self.suffix_prefill_fn(cache_shardings)

        if cache_shardings is None:
            if self._engine_paged is None:
                self._engine_paged = build()
            return self._engine_paged
        leaves, treedef = jax.tree_util.tree_flatten(cache_shardings)
        key = (treedef, tuple(leaves))
        if key not in self._mesh_paged_fns:
            self._mesh_paged_fns[key] = build()
        return self._mesh_paged_fns[key]

    # -- instrumentation --------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        """The co-design scorecard: how sparse, how shared, how padded."""
        plans = [p.plan if isinstance(p, QuantPlan) else p
                 for p in self.packs.values()
                 if isinstance(p, (RowPackPlan, QuantPlan))]
        unique = {pattern_key(p) for p in self.packs.values()}
        union = [s["union_overhead"] for s in self.export_stats.values()
                 if isinstance(s, dict) and "union_overhead" in s]
        st = self.registry.stats
        out = {
            "family": self.cfg.family,
            "arch": self.cfg.arch,
            "backend": self.spec.backend,
            "tile": list(self.spec.tile),
            "packed_projections": len(self.packs),
            "unique_patterns": len(unique),
            "density": (float(np.mean([p.density
                                       for p in self.packs.values()]))
                        if self.packs else None),
            "union_overhead": float(np.mean(union)) if union else None,
            "padded_flop_ratio": (float(np.mean([p.padding_waste
                                                 for p in plans]))
                                  if plans else None),
            "registry": {"hits": st.hits, "misses": st.misses,
                         "reuse_rate": st.reuse_rate},
        }
        # autotune verdicts (backend='auto'): measured winner per layer
        # group + how often the on-disk winner cache answered
        auto = {k: s["autotune"] for k, s in self.export_stats.items()
                if isinstance(s, dict) and "autotune" in s}
        if auto:
            out["autotune"] = {
                "backends": {k: a["backend"] for k, a in auto.items()},
                "cache_hits": sum(1 for a in auto.values()
                                  if a.get("cache_hit")),
                "cache_misses": sum(1 for a in auto.values()
                                    if not a.get("cache_hit")),
                "mode": next(iter(auto.values())).get("mode"),
            }
        qs = self.quant_stats()
        if qs:
            out["quant"] = qs
        if self.mesh is not None or self.spec.mesh_shape is not None:
            out["sharding"] = self._sharding_stats()
        if self.stats_at_save is not None:
            out["registry_at_save"] = self.stats_at_save.get("registry")
        return out

    def pack_bytes(self) -> Tuple[int, int]:
        """(total, per-device) bytes of the packed projection values in the
        params tree. Per-device accounting follows each leaf's placement
        (``sharding.shard_shape``); unplaced trees count fully on one
        device. Quantized packs count both their qvalues AND their scale
        arrays -- the scales are real pack traffic. Shared by ``stats()``
        and benchmarks/serving_bench.py."""
        targets = set()
        for key, pk in self.packs.items():
            targets.add(key + "/w")
            if isinstance(pk, QuantPlan):
                targets.add(key + "/scale")
        total = per_dev = 0

        def visit(path, leaf):
            nonlocal total, per_dev
            if _norm_path(_path_name(path)) not in targets:
                return leaf
            nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            total += nbytes
            shard_shape = (leaf.sharding.shard_shape(leaf.shape)
                           if hasattr(leaf, "sharding") else leaf.shape)
            per_dev += int(np.prod(shard_shape)) * leaf.dtype.itemsize
            return leaf
        jax.tree_util.tree_map_with_path(visit, self.params)
        return total, per_dev

    def quant_stats(self) -> Optional[Dict[str, Any]]:
        """Quantized-pack accounting: bytes actually stored (qvalues +
        scales, total and per-device) vs the fp32-equivalent footprint of
        the same packs, plus the worst export-time round-trip error. None
        when nothing is quantized (the common case; engine ``stats_dict()``
        forwards this section only when it exists)."""
        qpacks = {k: p for k, p in self.packs.items()
                  if isinstance(p, QuantPlan)}
        if not qpacks:
            return None
        wkeys = {k + "/w" for k in qpacks}
        skeys = {k + "/scale" for k in qpacks}
        acc = {"w": 0, "w_dev": 0, "scale": 0, "scale_dev": 0,
               "fp32": 0, "fp32_dev": 0}

        def visit(path, leaf):
            name = _norm_path(_path_name(path))
            if name not in wkeys and name not in skeys:
                return leaf
            n = int(np.prod(leaf.shape))
            shard_shape = (leaf.sharding.shard_shape(leaf.shape)
                           if hasattr(leaf, "sharding") else leaf.shape)
            nd = int(np.prod(shard_shape))
            if name in wkeys:
                acc["w"] += n * leaf.dtype.itemsize
                acc["w_dev"] += nd * leaf.dtype.itemsize
                acc["fp32"] += n * 4          # the same values stored fp32
                acc["fp32_dev"] += nd * 4
            else:
                acc["scale"] += n * leaf.dtype.itemsize
                acc["scale_dev"] += nd * leaf.dtype.itemsize
            return leaf
        jax.tree_util.tree_map_with_path(visit, self.params)
        qbytes = acc["w"] + acc["scale"]
        qdev = acc["w_dev"] + acc["scale_dev"]
        errs = [s["quant"] for s in self.export_stats.values()
                if isinstance(s, dict) and "quant" in s]
        out = {
            "pack_quant": self.spec.pack_quant,
            "quantized_packs": len(qpacks),
            "total_packs": len(self.packs),
            "qdtype": next(iter(qpacks.values())).qdtype,
            "granularities": sorted({p.granularity
                                     for p in qpacks.values()}),
            "quant_bytes_total": qbytes,
            "quant_bytes_per_device": qdev,
            "scale_bytes_total": acc["scale"],
            "fp32_equiv_bytes_total": acc["fp32"],
            "fp32_equiv_bytes_per_device": acc["fp32_dev"],
            "compression_ratio": (acc["fp32"] / qbytes if qbytes else None),
        }
        if errs:
            out["max_abs_err"] = max(e["max_abs_err"] for e in errs)
            out["max_rel_err"] = max(e["rel_err"] for e in errs)
        return out

    def _sharding_stats(self) -> Dict[str, Any]:
        """Per-shard accounting of the mesh path: how the pack bytes split
        across devices, which packs actually sharded, and the per-shard
        registry hit/miss counts collected at export."""
        total, per_dev = self.pack_bytes()
        sharded = {k: (p.plan if isinstance(p, QuantPlan) else p)
                   for k, p in self.packs.items()
                   if isinstance(p.plan if isinstance(p, QuantPlan) else p,
                                 ShardedPlan)}
        shard_meta = self.export_stats.get("__sharding__") or {}
        out = {
            "mesh_shape": (list(self.spec.mesh_shape)
                           if self.spec.mesh_shape else None),
            "partition": self.spec.partition,
            "n_shards": self.spec.model_shards,
            "sharded_packs": len(sharded),
            "replicated_packs": len(self.packs) - len(sharded),
            "pack_bytes_total": total,
            "pack_bytes_per_device": per_dev,
            "per_shard_registry": {
                str(s): dict(v)
                for s, v in (shard_meta.get("per_shard") or {}).items()},
            "axes": {k: p.shard_axis for k, p in sharded.items()},
        }
        if sharded:
            uniq = {p.fingerprint for p in sharded.values()}
            out["unique_sharded_patterns"] = len(uniq)
        return out

    # -- persistence ------------------------------------------------------
    def save(self, path: str) -> str:
        """Persist params + static patterns + spec/config under ``path``
        (a CheckpointStore directory). Export never re-runs on load."""
        store = CheckpointStore(path, keep=1)
        arrays, pack_meta = packs_to_arrays(self.packs)
        meta = {
            "spec": self.spec.to_dict(),
            "cfg": config_to_dict(self.cfg),
            "tree": tree_spec(self.params),
            "packs": pack_meta,
            "export_stats": self.export_stats,
            "stats": self.stats(),
        }
        store.save(SERVABLE_STEP, self.params, blocking=True,
                   extra={"servable": meta})
        step_dir = os.path.join(path, f"step_{SERVABLE_STEP:09d}")
        np.savez(os.path.join(step_dir, _PACKS_FILE), **arrays)
        return path


def prepare_servable(params, cfg: ModelConfig, spec: ServingSpec = None, *,
                     registry: Optional[PatternRegistry] = None) -> Servable:
    """Run the full prune -> export -> plan -> cache pipeline for any family.

    ``params`` are dense training-form weights (already-pruned weights with
    ``spec.prune='none'``). The returned Servable's weights are in packed
    serving form; the original tree is not modified.
    """
    spec = spec or ServingSpec()
    registry = registry if registry is not None else PatternRegistry()
    mesh = make_serving_mesh(spec) if spec.mesh_shape is not None else None

    if spec.prune == "oneshot":
        pruned, _ = oneshot_prune(params, spec.sparsity_config())
    elif spec.prune == "tied":
        pruned, _ = tied_prune(params, spec.sparsity_config())
    else:
        pruned = params

    if spec.backend == "dense":     # negative control: no BSR support
        if mesh is not None:
            pruned = jax.device_put(
                pruned, serving_param_shardings(pruned, {}, mesh))
        return Servable(pruned, cfg, spec, {}, registry, export_stats={},
                        mesh=mesh)

    chooser = None
    if spec.backend == "plan_pallas":
        # pinned, not measured: every pack serves through the compiled
        # plan-consuming kernel (export wraps each plan in a PlanChoice);
        # the chooser protocol only needs backend/cache_hit/mode
        import types

        def chooser(pack, shard=None):
            return types.SimpleNamespace(backend="plan_pallas",
                                         cache_hit=False, mode="pinned")
    elif spec.backend == "auto":
        from repro.kernels.autotune import choose_backend

        def chooser(pack, shard=None):
            # sharded serving has exactly two layouts with a mesh story
            # (ShardedPlan and dense-via-GSPMD, plus the quantized plan
            # when pack_quant asks for it); the winner is still keyed per
            # (pattern, shard, device count, quant, value dtype) on disk
            if shard and shard[0] > 1:
                cands = ("dense", "plan")
                if spec.pack_quant != "none":
                    cands = cands + ("plan_q8",)
            else:
                cands = None    # choose_backend adds the q8 arms per quant
            return choose_backend(pack, m=spec.autotune_m,
                                  candidates=cands, shard=shard,
                                  quant=spec.pack_quant)

    sparse_params, packs, stats = export_params(
        pruned, cfg, tile=spec.tile, fuse_qkv=spec.fuse_qkv,
        cross_layer_union=spec.cross_layer_union,
        include_ffn=spec.include_ffn, use_plans=spec.use_plans,
        registry=registry, backend_chooser=chooser,
        n_shards=spec.model_shards, pack_quant=spec.pack_quant)
    if spec.dtype is not None and packs:
        jdtype = jnp.bfloat16 if spec.dtype == "bfloat16" else jnp.float32
        sparse_params = _cast_packed(sparse_params, packs, jdtype)
    if mesh is not None:
        packs = attach_mesh(packs, mesh)
        sparse_params = jax.device_put(
            sparse_params, serving_param_shardings(sparse_params, packs,
                                                   mesh))
    return Servable(sparse_params, cfg, spec, packs, registry,
                    export_stats=stats, mesh=mesh)


def load_servable(path: str, *, registry: Optional[PatternRegistry] = None,
                  chaos=None) -> Servable:
    """Restore a saved Servable: params via ``CheckpointStore.restore``,
    patterns via the fingerprint-keyed pack codec. No pruning, packing, or
    plan construction re-runs; the load-time registry only pays one build
    per unique pattern (the saved reuse counters stay readable under
    ``stats()['registry_at_save']``).

    A truncated / corrupt / incomplete artifact raises
    :class:`~repro.serving.serialize.ServableLoadError` naming the
    offending piece (the npz leaf when one is identifiable) instead of
    surfacing a zlib/zip/KeyError traceback from deep inside the codec.
    ``chaos`` (a ``repro.runtime.chaos.ChaosInjector``) fires the
    ``servable.load_packs`` site just before the archive is read."""
    store = CheckpointStore(path)
    try:
        meta = store.meta(SERVABLE_STEP)["servable"]
    except Exception as e:
        raise ServableLoadError(
            f"servable meta unreadable under {path} "
            f"({type(e).__name__}: {e})") from e
    cfg = config_from_dict(meta["cfg"])
    spec = ServingSpec.from_dict(meta["spec"])
    params = store.restore(build_like(meta["tree"]), step=SERVABLE_STEP)
    step_dir = os.path.join(path, f"step_{SERVABLE_STEP:09d}")
    registry = registry if registry is not None else PatternRegistry()
    packs_path = os.path.join(step_dir, _PACKS_FILE)
    if chaos is not None:
        from repro.runtime.chaos import SITE_LOAD_PACKS
        chaos.fire(SITE_LOAD_PACKS, path=packs_path)
    try:
        npz = np.load(packs_path)
    except Exception as e:
        raise ServableLoadError(
            f"pack archive {packs_path} unreadable "
            f"({type(e).__name__}: {e})") from e
    with npz:
        packs = packs_from_arrays(meta["packs"], LeafReader(npz, packs_path),
                                  registry)
    mesh = None
    if spec.mesh_shape is not None:
        # the artifact stores shard-partitioned packs; re-placement (and
        # the mesh the linear hook pins shardings to) is rebuilt per
        # process from the spec
        mesh = make_serving_mesh(spec)
        packs = attach_mesh(packs, mesh)
        params = jax.device_put(
            params, serving_param_shardings(params, packs, mesh))
    return Servable(params, cfg, spec, packs, registry,
                    export_stats=meta.get("export_stats"),
                    stats_at_save=meta.get("stats"), mesh=mesh)
