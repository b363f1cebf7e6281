"""Shared layer primitives: norms, activations, RoPE, linear init/apply.

Everything is a pure function over explicit param dicts (no module framework
dependency); params are plain pytrees so they shard, scan, and checkpoint
uniformly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def normal_init(key, shape, scale=0.02, dtype=jnp.float32):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


# -- norms -------------------------------------------------------------------

def rms_norm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))
            ).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def init_norm(key, d, kind="rms", dtype=jnp.float32):
    if kind == "rms":
        return {"scale": jnp.zeros((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def apply_norm(p, x, kind="rms"):
    if kind == "rms":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


# -- activations --------------------------------------------------------------

def act_fn(name):
    return {"gelu": jax.nn.gelu, "silu": jax.nn.silu, "relu": jax.nn.relu}[name]


# -- RoPE ---------------------------------------------------------------------

def rope_freqs(head_dim, rotary_fraction=1.0, theta=10000.0):
    rot = int(head_dim * rotary_fraction)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv, rot


def apply_rope(x, positions, *, theta=10000.0, rotary_fraction=1.0):
    """x: (..., S, H, D); positions: (..., S) int32. Pairs (x_i, x_{i+rot/2})
    rotated; trailing (1-fraction) dims pass through (chatglm-style partial)."""
    d = x.shape[-1]
    inv, rot = rope_freqs(d, rotary_fraction, theta)
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    ang = positions[..., None].astype(jnp.float32) * inv          # (..., S, rot/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2, x_pass.astype(jnp.float32)],
                           axis=-1).astype(x.dtype)


# -- linear (dense or block-sparse) -------------------------------------------

def init_linear(key, d_in, d_out, dtype=jnp.float32, scale=0.02):
    """Weight stored (d_out, d_in): y = x @ w.T -- matches the BSR layout."""
    return {"w": normal_init(key, (d_out, d_in), scale, dtype)}


def tp_constrain(y, pack):
    """The tensor-parallel sharding hook for plan-backed projections
    (kernels/exec_plan.ShardedPlan with a mesh attached by
    ``prepare_servable``):

      * column-parallel (``shard_axis='out'``): pin the output feature dim
        to the mesh "model" axis -- activations stay sharded into the next
        (row-parallel) projection, no gather between them;
      * row-parallel (``shard_axis='in'``): pin the feature dim replicated
        -- THE single psum per layer that folds the per-device partial
        products (the plan's segment-sum) back together.

    The leading (batch/slot) dim keeps its "data" sharding when the mesh
    has one -- a None there would constrain it REPLICATED and force a
    per-layer all-gather of activations under partition='tp+dp'.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = [None] * y.ndim
    dp = dict(pack.mesh.shape).get("data", 1)
    if y.ndim >= 2 and dp > 1 and y.shape[0] % dp == 0:
        spec[0] = "data"    # batch-1 prefill sub-caches stay replicated
    if pack.shard_axis == "out":
        spec[-1] = "model"
    return jax.lax.with_sharding_constraint(
        y, NamedSharding(pack.mesh, P(*spec)))


def linear(p, x, pack=None, backend=None):
    """Dense or block-sparse projection.

    ``pack`` is static pattern metadata (from repro.serving.export), one of:
      * a ``RowPackPlan`` -- ``p['w']`` holds row-grouped values
        (R, P, bn, bk) and the precomputed-plan fast path executes
        (kernels/exec_plan.py; no per-call pattern work at all); its
        ``ShardedPlan`` subclass additionally carries the tensor-parallel
        vrow partitioning and (when a mesh is attached) pins the output
        sharding via :func:`tp_constrain`;
      * a ``PlanChoice`` -- the same row-grouped layout pinned to a
        plan-consuming execution backend (``'plan_pallas'`` = the compiled
        Pallas kernel driven by the plan's spill schedule);
      * a ``QuantPlan`` -- ``p['w']`` holds int8/fp8 row-grouped values and
        ``p['scale']`` the per-block (or per-row-group) fp32 scales; the
        dequant-fused plan matmul executes (``pack.backend`` picks the XLA
        composition vs the compiled kernel), and a ShardedPlan inner keeps
        the tensor-parallel constraint;
      * a ``KernelBSR`` -- ``p['w']`` holds packed tile values (nnzt, bn, bk)
        and the matmul dispatches through ``bsr_linear``'s backends;
      * an ``autotune.BackendChoice`` -- a KernelBSR pattern pinned to the
        backend the autotuner measured fastest for it (backend='auto');
      * an ``autotune.MaskedPack`` -- ``p['w']`` stays a DENSE (N, K)
        weight and the tile-skipping ``masked`` kernel executes.
    """
    if pack is not None:
        from repro.kernels.exec_plan import (PlanChoice, QuantPlan,
                                             RowPackPlan, ShardedPlan,
                                             plan_matmul)
        if isinstance(pack, QuantPlan):
            from repro.kernels.ops import plan_q_dispatch
            y = plan_q_dispatch(x, p["w"], p["scale"], pack.plan,
                                backend=pack.backend)
            if (isinstance(pack.plan, ShardedPlan)
                    and pack.plan.mesh is not None):
                y = tp_constrain(y, pack.plan)
            return y
        if isinstance(pack, PlanChoice):
            from repro.kernels.ops import plan_dispatch
            return plan_dispatch(x, p["w"], pack.plan, backend=pack.backend)
        if isinstance(pack, RowPackPlan):
            y = plan_matmul(x, p["w"], pack)
            if isinstance(pack, ShardedPlan) and pack.mesh is not None:
                y = tp_constrain(y, pack)
            return y
        from repro.kernels.autotune import BackendChoice, MaskedPack
        if isinstance(pack, BackendChoice):
            backend, pack = pack.backend, pack.pack
        if isinstance(pack, MaskedPack):
            from repro.kernels.bsr_matmul import masked_matmul
            lead = x.shape[:-1]
            y = masked_matmul(x.reshape(-1, x.shape[-1]), p["w"],
                              jnp.asarray(pack.tile_mask), tile=pack.tile)
            return y.reshape(*lead, pack.shape[0])
        from repro.kernels.ops import bsr_matmul  # local import, cycle-free
        from repro.kernels.bsr_matmul import KernelBSR
        kb = KernelBSR(p["w"], pack.row_id, pack.col_id, pack.t_perm,
                       pack.real_nnzt, pack.shape, pack.tile)
        return bsr_matmul(x, kb, backend)
    return jnp.einsum("...k,nk->...n", x, p["w"])


def prefill_conv_history(x, valid, length, width, dtype):
    """Conv-cache state after a one-pass prompt prefill: the last ``width``
    pre-conv inputs of the real prompt. ``x``: (B, S, C) bucket-padded
    inputs, ``valid``: (1, S, 1) real-token mask, ``length`` (traced OK) the
    prompt length. Masking then left-padding ``width`` zeros makes prompts
    shorter than the conv window zero-fill exactly like a fresh decode
    cache. Shared by the SSM and RG-LRU prefill paths."""
    b = x.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((b, width) + x.shape[2:], x.dtype),
         jnp.where(valid, x, 0)], axis=1)
    return jax.lax.dynamic_slice_in_dim(
        padded, jnp.asarray(length, jnp.int32), width, axis=1).astype(dtype)


# -- paged KV primitives ------------------------------------------------------
#
# A paged cache replaces per-slot contiguous KV storage (B, T, ...) with a
# pool of fixed-size pages (n_pages, page_size, ...) plus a per-slot page
# table (B, T // page_size) of physical page ids (-1 = unmapped). The
# serving engine owns allocation (repro/serving/paging.py); the model layer
# only needs the three pure device ops below. Index discipline: -1 must
# never reach a device gather/scatter directly (JAX wraps negative indices);
# gathers clip into range and mask by pos_map, scatters map invalid rows to
# n_pages (out of bounds HIGH), which jit scatter semantics DROP.
#
# The scan-stacked layers keep their pools stacked (P, n_pages, ...) in the
# layer scan's carry (models/lm.py); ``layer`` then names the layer whose
# pool an op reads or writes, so no layer's pool is ever sliced out.

import dataclasses as _dataclasses


@_dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Static paging geometry threaded into ``init_cache``: a pool of
    ``n_pages`` pages of ``page_size`` positions each, addressed through
    per-slot page tables covering ``cache_len // page_size`` entries."""

    page_size: int
    n_pages: int

    def __post_init__(self):
        if self.page_size < 1 or self.n_pages < 1:
            raise ValueError(f"bad paged layout: page_size={self.page_size} "
                             f"n_pages={self.n_pages}")

    def table_width(self, cache_len: int) -> int:
        if cache_len % self.page_size:
            raise ValueError(
                f"cache_len={cache_len} not divisible by "
                f"page_size={self.page_size}")
        return cache_len // self.page_size


def _pool_lead(layer):
    """Index prefix of a pool: () for one layer's (N, ps, ...) pool,
    (layer,) for a stacked (P, N, ps, ...) one."""
    return () if layer is None else (layer,)


def paged_view(pages, page_table, pos_map, layer=None):
    """Gather a slot-contiguous (B, T, ...) view out of a page pool.

    ``pages``: (N, ps, ...), or (P, N, ps, ...) read at ``layer``;
    ``page_table``: (B, NP) int32 page ids (-1 = unmapped); ``pos_map``:
    (B, T = NP*ps) absolute positions (-1 = empty). Unmapped/unwritten
    positions read as EXACT zeros -- the view is then elementwise identical
    to the dense slot cache the same writes would have produced (dense
    caches zero-init and zero-reset), which is what makes the paged decode
    path bit-exact against the dense oracle."""
    lead = _pool_lead(layer)
    b, npg = page_table.shape
    n, ps, *row = pages.shape[len(lead):]
    safe = jnp.clip(page_table, 0, n - 1)            # gather: clip, mask below
    view = pages[lead + (safe,)]                     # (B, NP, ps, ...)
    view = view.reshape((b, npg * ps, *row))
    keep = (pos_map >= 0).reshape((b, npg * ps) + (1,) * len(row))
    return jnp.where(keep, view, jnp.zeros_like(view))


def paged_row_write(pages, page_table, positions, val, active, layer=None):
    """Scatter one new position per batch row into the pool: row ``i``'s
    value lands in page ``page_table[i, positions[i] // ps]`` at offset
    ``positions[i] % ps`` (of layer ``layer``'s pool when ``pages`` is
    stacked); ``val[i]`` is reshaped to the pool's row. Inactive/unmapped
    rows are redirected to page id ``n_pages`` -- out of bounds, so the jit
    scatter DROPS them (the paged analogue of attention._masked_row_write)."""
    lead = _pool_lead(layer)
    n, ps, *row = pages.shape[len(lead):]
    npg = page_table.shape[1]
    posv = jnp.maximum(positions, 0)
    rows = jnp.arange(page_table.shape[0])
    pp = page_table[rows, jnp.clip(posv // ps, 0, npg - 1)]
    pp = jnp.where(active & (pp >= 0), pp, n)        # OOB-high => dropped
    return pages.at[lead + (pp, posv % ps)].set(val.reshape(len(rows), *row))


def paged_bulk_write(pages, page_row, vals):
    """Scatter a slot-contiguous tensor into the pool pages of ONE slot:
    ``vals`` (NP*ps, ...) reshaped to (NP, ps, ...) lands page-wise at the
    ids in ``page_row`` (NP,); entries < 0 (unallocated table slots) are
    redirected out of bounds and dropped. Used to insert a dense batch-1
    prefill result into a slot's pages -- every allocated page is fully
    (re)written, so recycled pages cannot leak stale state."""
    n, ps = pages.shape[0], pages.shape[1]
    npg = page_row.shape[0]
    dst = jnp.where(page_row >= 0, page_row, n)
    return pages.at[dst].set(vals.reshape((npg, ps) + pages.shape[2:]))


def init_mlp(key, d_model, d_ff, act="swiglu", dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    if act in ("swiglu", "geglu"):
        return {"wi": init_linear(k1, d_model, d_ff, dtype),
                "wg": init_linear(k2, d_model, d_ff, dtype),
                "wo": init_linear(k3, d_ff, d_model, dtype)}
    return {"wi": init_linear(k1, d_model, d_ff, dtype),
            "wo": init_linear(k3, d_ff, d_model, dtype)}


def apply_mlp(p, x, act="swiglu", packs=None, backend=None):
    def pk(name):
        return None if packs is None else packs.get(name)
    if act in ("swiglu", "geglu"):
        g = jax.nn.silu if act == "swiglu" else jax.nn.gelu
        h = g(linear(p["wg"], x, pk("wg"), backend)) * linear(p["wi"], x, pk("wi"), backend)
    else:
        h = act_fn(act)(linear(p["wi"], x, pk("wi"), backend))
    return linear(p["wo"], h, pk("wo"), backend)
