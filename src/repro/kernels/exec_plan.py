"""Precomputed execution plans for block-sparse serving (docs/PERF.md).

SparseRT's lesson (and the paper's TVM task-buffer mechanism, §2.2) is that a
sparse op should pay for its pattern exactly once, ahead of time. The seed
``rowpack`` backend violated this twice on the serving hot path:

  * the row-grouped layout (``col_idx``/``slot``) was rebuilt with a Python
    loop at **every trace** of the op, and
  * the stored tile values were re-scattered from the packed ``(nnzt, bn, bk)``
    layout into the row-grouped layout with a ``zeros().at[].set()`` inside
    **every jitted call** -- pure memory traffic on a path the Sparsity
    Roofline says is traffic-bound already.

A :class:`RowPackPlan` moves all pattern-dependent work offline. It is frozen
host metadata (numpy, hashable by pattern fingerprint) computed once at pack
time; weight values are stored *already row-grouped*, so the per-call path is
one gather of ``x``, one batched matmul, and (only when the plan spilled
rows) one segment-sum. Plans are cached through
``core.pattern_reuse.PatternRegistry`` -- identical patterns (e.g. the 12
cross-layer-unioned BERT encoder layers) share one plan and, because the plan
hashes by fingerprint, one compiled executable.

Offline scheduling
------------------
``rowpack`` pads every block row to P = max tiles/row, so a skewed pattern
(binomial row occupancy at serving densities) wastes 1.5-2.5x the real FLOPs
on padding. Because the plan is built ahead of time it instead *chooses* a
row capacity P that minimizes total padded slots (subject to a GEMM-
efficiency floor on the inner dimension P*bk) and spills the overflow tiles
of heavy rows into extra **virtual rows**; a segment-sum folds virtual rows
back into their real output rows. For uniform patterns no row spills and the
schedule degenerates to the seed layout with the scatter removed.

Layout, for a tile-BSR weight ``W (N, K)`` with ``R = N/bn`` block rows,
``V >= R`` virtual rows and ``P`` slots per virtual row:

  * ``col_idx (V, P)``    -- block-column of the tile in each slot
                             (0 for padding slots: they multiply zero data);
  * ``slot_mask (V, P)``  -- True where a real tile lives (grads of padding
                             slots are forced to zero: pruned blocks stay
                             dead);
  * ``row_of_vrow (V,)``  -- owning block row of each virtual row;
  * data ``(V, P, bn, bk)`` -- tile values, already grouped by virtual row.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pattern_reuse import PatternRegistry
from repro.kernels.bsr_matmul import KernelBSR

# GEMM-efficiency floor for the batched matmul's inner dimension P*bk:
# below this, small-P schedules degenerate into gather-style batch-1 work.
_MIN_INNER = 128


def kernel_pattern_fingerprint(pack: KernelBSR) -> bytes:
    """Hashable fingerprint of a KernelBSR *structure* (not values) -- the
    task-identity key for plan reuse, mirroring core.bsr.pattern_fingerprint."""
    header = np.array([*pack.shape, *pack.tile, pack.nnzt, pack.real_nnzt],
                      dtype=np.int64)
    return (header.tobytes()
            + np.asarray(pack.row_id, np.int32).tobytes()
            + np.asarray(pack.col_id, np.int32).tobytes())


@dataclasses.dataclass(frozen=True, eq=False)
class RowPackPlan:
    """Frozen row-grouped execution plan for one sparsity pattern.

    All fields are host numpy / python scalars: the plan is static metadata
    baked into specializations. Hash/eq go through ``fingerprint`` so plans
    can key jit caches -- two layers with identical patterns share one
    executable even if their plan objects differ.
    """

    col_idx: np.ndarray       # (V, P) int32 block-col per slot
    slot_mask: np.ndarray     # (V, P) bool, True where a real tile lives
    row_of_vrow: np.ndarray   # (V,) int32 owning block row of each vrow
    vrow: np.ndarray          # (real_nnzt,) int32 virtual row of each tile
    slot: np.ndarray          # (real_nnzt,) int32 slot of each tile
    shape: Tuple[int, int]    # (N, K)
    tile: Tuple[int, int]     # (bn, bk)
    nnzt: int                 # packed tile count incl. padding tiles
    real_nnzt: int            # stored tiles that are not padding
    fingerprint: bytes        # kernel_pattern_fingerprint of the source pack

    @property
    def n_brows(self) -> int:
        return self.shape[0] // self.tile[0]

    @property
    def n_bcols(self) -> int:
        return self.shape[1] // self.tile[1]

    @property
    def n_vrows(self) -> int:
        return int(self.col_idx.shape[0])

    @property
    def p_max(self) -> int:
        return int(self.col_idx.shape[1])

    @property
    def spilled(self) -> bool:
        """True when heavy rows overflowed into virtual rows (the per-call
        path then folds them back with one segment-sum)."""
        return self.n_vrows != self.n_brows

    @property
    def density(self) -> float:
        return self.real_nnzt / max(1, self.n_brows * self.n_bcols)

    @property
    def padding_waste(self) -> float:
        """Total slots / real tiles (1.0 = zero padding) -- the FLOP
        overhead factor of the schedule (rowpack's fixed max-P layout sits
        at R*max(c)/nnzt)."""
        return self.n_vrows * self.p_max / max(1, self.real_nnzt)

    def __hash__(self):
        return hash(self.fingerprint)

    def __eq__(self, other):
        return (isinstance(other, RowPackPlan)
                and self.fingerprint == other.fingerprint)


@dataclasses.dataclass(frozen=True, eq=False)
class ShardedPlan(RowPackPlan):
    """A RowPackPlan whose virtual-row axis is partitioned into ``n_shards``
    contiguous, equal-size groups -- one per device of a tensor-parallel
    "model" mesh axis (launch/sharding.py conventions).

    ``shard_axis`` selects the TP layout:

      * ``'out'`` (column-parallel: wq/wk/wv/wqkv/wi/wg): shard ``s`` owns
        output block rows ``[s*R/S, (s+1)*R/S)``; its vrows reference only
        those rows, so the row-grouped values ``(V, P, bn, bk)`` sharded
        over vrows place each shard's tiles on exactly one device and the
        output feature dim comes out model-sharded;
      * ``'in'`` (row-parallel: wo): shard ``s`` owns input block columns
        ``[s*C/S, (s+1)*C/S)``; every shard's vrows map to *global* output
        rows, so the plan's segment-sum doubles as the per-layer psum that
        folds the partial products back together.

    Either way the per-call math is exactly :func:`plan_linear` -- the
    shard structure lives entirely in how vrows/values are laid out, which
    is why a sharded plan is *also* a valid single-device plan (exact
    fallback when no mesh is active). ``spilled`` is forced True: the
    segment-sum is what reassembles (or psums) the per-shard partials.

    ``shard_fingerprints`` identify each shard's sub-pattern -- the
    per-shard registry / autotune cache keys (a winner measured for one
    shard's pattern never answers for a different shard or device count).
    ``mesh`` is attached by ``prepare_servable`` (never serialized, never
    part of the fingerprint): when set, ``models/common.linear`` pins the
    output sharding (column-parallel) or the psum point (row-parallel).
    """

    n_shards: int = 1
    shard_axis: str = "out"            # 'out' = column-parallel, 'in' = row
    shard_fingerprints: Tuple[bytes, ...] = ()
    mesh: Optional[object] = None      # jax.sharding.Mesh, attached late

    @property
    def spilled(self) -> bool:
        # per-shard partials always fold through the segment-sum (for
        # 'in'-sharding it IS the psum), even if vrow/row counts collide
        return True

    @property
    def vrows_per_shard(self) -> int:
        return self.n_vrows // max(1, self.n_shards)

    def with_mesh(self, mesh) -> "ShardedPlan":
        return dataclasses.replace(self, mesh=mesh)

    def __hash__(self):
        return hash(self.fingerprint)

    def __eq__(self, other):
        return (isinstance(other, ShardedPlan)
                and self.fingerprint == other.fingerprint)


# a spill schedule reassociates row sums and adds segment-sum + batch-count
# overhead, so it must buy a decisive FLOP reduction to be worth it; below
# this saving the no-spill layout (strictly cheaper than rowpack: same
# matmul, no per-call scatter) is kept.
_SPILL_MIN_SAVING = 0.25


def _choose_capacity(counts: np.ndarray, bk: int) -> int:
    """Pick the per-vrow slot capacity P minimizing total padded slots
    ``(R + spill_rows(P)) * P``, subject to the inner-dimension floor
    P*bk >= _MIN_INNER (ties -> larger P: fewer vrows, fewer segment adds)
    and to the spill schedule saving at least ``_SPILL_MIN_SAVING`` of the
    no-spill slots.

    Fully offline -- this is the schedule choice SparseRT makes at codegen
    time and rowpack (fixed P = max(counts)) cannot make at all.
    """
    cmax = max(1, int(counts.max()))
    p_lo = min(cmax, max(1, -(-_MIN_INNER // bk)))
    cand = np.arange(p_lo, cmax + 1, dtype=np.int64)
    extra = np.ceil(np.maximum(counts[None, :] - cand[:, None], 0)
                    / cand[:, None]).sum(axis=1)
    slots = (len(counts) + extra) * cand
    best = slots.min()
    if best > (1.0 - _SPILL_MIN_SAVING) * len(counts) * cmax:
        return cmax
    return int(cand[np.nonzero(slots <= best * 1.02)[0][-1]])


def build_plan(pack: KernelBSR) -> RowPackPlan:
    """Derive the spill-scheduled row-grouped layout on host, once.

    Padding tiles (``real_nnzt <= j < nnzt``) are dropped: their data is zero
    by the pack_bsr contract, so they only wasted a row slot in the seed
    layout. Replaces the per-trace Python loop of the old ``_rowpack_static``
    with vectorized numpy.
    """
    rows = np.asarray(pack.row_id[: pack.real_nnzt], dtype=np.int64)
    cols = np.asarray(pack.col_id[: pack.real_nnzt], dtype=np.int64)
    r = pack.n_brows
    counts = np.bincount(rows, minlength=r)
    p = _choose_capacity(counts, pack.tile[1])
    # rank of each tile within its row (stable, preserves column order)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    order = np.argsort(rows, kind="stable")
    rank = np.empty(rows.shape[0], np.int64)
    rank[order] = np.arange(rows.shape[0]) - starts[rows[order]]
    # spill layout: row r owns vrow r plus ceil((c_r - P)+ / P) extra vrows
    n_spill = np.ceil(np.maximum(counts - p, 0) / p).astype(np.int64)
    spill_base = r + np.concatenate([[0], np.cumsum(n_spill)[:-1]])
    v = int(r + n_spill.sum())
    chunk = rank // p                      # 0 = home vrow, >=1 = spill chunk
    vrow = np.where(chunk == 0, rows, spill_base[rows] + chunk - 1)
    slot = rank % p
    col_idx = np.zeros((v, p), np.int32)
    col_idx[vrow, slot] = cols
    slot_mask = np.zeros((v, p), bool)
    slot_mask[vrow, slot] = True
    row_of_vrow = np.empty((v,), np.int64)
    row_of_vrow[:r] = np.arange(r)
    for rr in np.nonzero(n_spill)[0]:
        row_of_vrow[spill_base[rr]: spill_base[rr] + n_spill[rr]] = rr
    return RowPackPlan(col_idx=col_idx, slot_mask=slot_mask,
                       row_of_vrow=row_of_vrow.astype(np.int32),
                       vrow=vrow.astype(np.int32), slot=slot.astype(np.int32),
                       shape=pack.shape, tile=pack.tile, nnzt=pack.nnzt,
                       real_nnzt=pack.real_nnzt,
                       fingerprint=kernel_pattern_fingerprint(pack))


# --------------------------------------------------------------------------
# sharded plans (tensor-parallel serving: launch/sharding.py conventions)
# --------------------------------------------------------------------------

def shard_divisible(pack: KernelBSR, n_shards: int, shard_axis: str) -> bool:
    """True when this pack can be partitioned into ``n_shards`` equal groups
    along ``shard_axis`` ('out' = output block rows, 'in' = input block
    cols) -- the same divisibility rule launch/sharding.spec_for_param
    applies to dense weights (indivisible dims replicate)."""
    dim = pack.n_brows if shard_axis == "out" else pack.n_bcols
    return n_shards >= 1 and dim % n_shards == 0 and dim >= n_shards

def _shard_layout(rows: np.ndarray, cols: np.ndarray, p: int):
    """Compressed row-grouped layout for ONE shard's tiles (pack order).

    ``rows`` are the *global* output block rows of this shard's tiles.
    Unlike :func:`build_plan` (one vrow per block row, empty rows padded),
    only rows actually present get vrows: at serving densities a shard owns
    a small fraction of each row's tiles, and empty-row slots would multiply
    the padding waste by ``n_shards``. Returns
    ``(col_idx (v, p), slot_mask, row_of_vrow (v,) global, vrow, slot)``.
    """
    if rows.size == 0:
        return (np.zeros((0, p), np.int32), np.zeros((0, p), bool),
                np.zeros((0,), np.int64), np.zeros((0,), np.int64),
                np.zeros((0,), np.int64))
    uniq, inv = np.unique(rows, return_inverse=True)
    counts = np.bincount(inv, minlength=len(uniq))
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    order = np.argsort(inv, kind="stable")
    rank = np.empty(rows.shape[0], np.int64)
    rank[order] = np.arange(rows.shape[0]) - starts[inv[order]]
    n_spill = np.ceil(np.maximum(counts - p, 0) / p).astype(np.int64)
    spill_base = len(uniq) + np.concatenate([[0], np.cumsum(n_spill)[:-1]])
    v = int(len(uniq) + n_spill.sum())
    chunk = rank // p
    vrow = np.where(chunk == 0, inv, spill_base[inv] + chunk - 1)
    slot = rank % p
    col_idx = np.zeros((v, p), np.int32)
    col_idx[vrow, slot] = cols
    slot_mask = np.zeros((v, p), bool)
    slot_mask[vrow, slot] = True
    row_of_vrow = np.empty((v,), np.int64)
    row_of_vrow[: len(uniq)] = uniq
    for rr in np.nonzero(n_spill)[0]:
        row_of_vrow[spill_base[rr]: spill_base[rr] + n_spill[rr]] = uniq[rr]
    return col_idx, slot_mask, row_of_vrow, vrow, slot


def shard_pattern_fingerprint(pack: KernelBSR, n_shards: int,
                              shard_axis: str, shard: int) -> bytes:
    """Fingerprint of ONE shard's sub-pattern -- the per-shard registry and
    autotune key (kernels/autotune.py keys winners on (digest, shard, m,
    device); two shards with identical sub-patterns share one key)."""
    rows = np.asarray(pack.row_id[: pack.real_nnzt], np.int64)
    cols = np.asarray(pack.col_id[: pack.real_nnzt], np.int64)
    if shard_axis == "out":
        per = pack.n_brows // n_shards
        sel = (rows // per) == shard
        lrows, lcols = rows[sel] % per, cols[sel]
        shape = (pack.shape[0] // n_shards, pack.shape[1])
    else:
        per = pack.n_bcols // n_shards
        sel = (cols // per) == shard
        lrows, lcols = rows[sel], cols[sel] % per
        shape = (pack.shape[0], pack.shape[1] // n_shards)
    header = np.array([*shape, *pack.tile, int(shard_axis == "in")], np.int64)
    return (b"shard:" + header.tobytes()
            + lrows.astype(np.int32).tobytes()
            + lcols.astype(np.int32).tobytes())


def build_sharded_plan(pack: KernelBSR, n_shards: int,
                       shard_axis: str = "out", *,
                       registry: Optional[PatternRegistry] = None,
                       shard_stats: Optional[dict] = None) -> ShardedPlan:
    """Partition ``pack`` into ``n_shards`` equal vrow groups (see
    :class:`ShardedPlan`). All shards share one slot capacity P and are
    padded to the max per-shard vrow count, so the combined vrow axis is
    exactly ``n_shards``-divisible -- the property that lets the values
    array shard over the mesh "model" axis with zero cross-device tiles.

    ``registry`` (optional) caches each shard's layout under its sub-pattern
    fingerprint -- identical layers (cross-layer union, scan-stacked groups)
    then reuse per-shard layouts, and ``shard_stats`` (dict, optional) is
    filled with per-shard hit/miss counts for ``Servable.stats()``.
    """
    if not shard_divisible(pack, n_shards, shard_axis):
        raise ValueError(
            f"pattern {pack.shape} @ tile {pack.tile} not divisible into "
            f"{n_shards} shards along {shard_axis!r}")
    rows = np.asarray(pack.row_id[: pack.real_nnzt], np.int64)
    cols = np.asarray(pack.col_id[: pack.real_nnzt], np.int64)
    bn, bk = pack.tile
    if shard_axis == "out":
        per = pack.n_brows // n_shards
        shard_of = rows // per
    else:
        per = pack.n_bcols // n_shards
        shard_of = cols // per
    # one capacity for every shard (uniform P = uniform padded layout)
    p = 1
    for s in range(n_shards):
        srows = rows[shard_of == s]
        if srows.size:
            counts = np.bincount(np.unique(srows, return_inverse=True)[1])
            p = max(p, _choose_capacity(counts, bk))

    layouts, fps = [], []
    for s in range(n_shards):
        idx = np.nonzero(shard_of == s)[0]
        fp = shard_pattern_fingerprint(pack, n_shards, shard_axis, s)
        fps.append(fp)
        # layouts are built (and registry-cached) in SHARD-LOCAL
        # coordinates -- the fingerprint describes the local sub-pattern,
        # so two shards with identical local structure must share a
        # position-independent layout; global offsets are re-applied at
        # assembly below
        lrows = rows[idx] - s * per if shard_axis == "out" else rows[idx]
        lcols = cols[idx] - s * per if shard_axis == "in" else cols[idx]

        def build(lrows=lrows, lcols=lcols):
            return _shard_layout(lrows, lcols, p)
        if registry is not None:
            key = ("plan_shard", shard_axis, p, fp)
            if shard_stats is not None:
                st = shard_stats.setdefault(s, {"hits": 0, "misses": 0})
                st["hits" if registry.peek(key) else "misses"] += 1
            layouts.append((idx, registry.cached(key, build)))
        else:
            layouts.append((idx, build()))

    v_max = max(1, max(lay[1][0].shape[0] for lay in layouts))
    col_idx = np.zeros((n_shards * v_max, p), np.int32)
    slot_mask = np.zeros((n_shards * v_max, p), bool)
    row_of_vrow = np.zeros((n_shards * v_max,), np.int64)
    vrow = np.zeros((pack.real_nnzt,), np.int64)
    slot = np.zeros((pack.real_nnzt,), np.int64)
    for s, (idx, (ci, sm, rov, vr, sl)) in enumerate(layouts):
        v = ci.shape[0]
        lo = s * v_max
        # globalize: 'out' shards own rows [s*per, (s+1)*per); 'in' shards
        # gather x block-cols [s*per, (s+1)*per). Padding vrows (>= v) keep
        # the shard's base row/col: they multiply zero data either way.
        if shard_axis == "out":
            col_idx[lo: lo + v] = ci
            row_of_vrow[lo: lo + v_max] = s * per
            row_of_vrow[lo: lo + v] = rov + s * per
        else:
            col_idx[lo: lo + v_max] = s * per
            col_idx[lo: lo + v] = ci + s * per
            row_of_vrow[lo: lo + v] = rov
        slot_mask[lo: lo + v] = sm
        vrow[idx] = lo + vr
        slot[idx] = sl
    header = np.array([n_shards, int(shard_axis == "in")], np.int64)
    fingerprint = (b"sharded:" + header.tobytes()
                   + kernel_pattern_fingerprint(pack))
    return ShardedPlan(
        col_idx=col_idx, slot_mask=slot_mask,
        row_of_vrow=row_of_vrow.astype(np.int32),
        vrow=vrow.astype(np.int32), slot=slot.astype(np.int32),
        shape=pack.shape, tile=pack.tile, nnzt=pack.nnzt,
        real_nnzt=pack.real_nnzt, fingerprint=fingerprint,
        n_shards=n_shards, shard_axis=shard_axis,
        shard_fingerprints=tuple(fps))


# --------------------------------------------------------------------------
# plan-keyed registry (the task buffer for execution plans)
# --------------------------------------------------------------------------

_PLAN_REGISTRY = PatternRegistry()


def default_plan_registry() -> PatternRegistry:
    """Process-wide plan task buffer (hit/miss stats included)."""
    return _PLAN_REGISTRY


def plan_for_pack(pack: KernelBSR,
                  registry: Optional[PatternRegistry] = None) -> RowPackPlan:
    """Cached plan lookup: identical patterns share one RowPackPlan (and via
    its fingerprint-hash, one compiled executable downstream)."""
    reg = registry if registry is not None else _PLAN_REGISTRY
    fp = kernel_pattern_fingerprint(pack)
    return reg.cached(("rowpack_plan", fp), lambda: build_plan(pack))


# --------------------------------------------------------------------------
# offline data re-layout (pack time, not call time)
# --------------------------------------------------------------------------

def pack_plan_data(plan: RowPackPlan, data) -> jax.Array:
    """(..., nnzt, bn, bk) packed tile values -> (..., V, P, bn, bk)
    row-grouped values. This is the scatter the seed backend paid on every
    forward call; here it runs once at export/pack time."""
    data = jnp.asarray(data)
    lead = data.shape[:-3]
    bn, bk = plan.tile
    d = data.reshape((-1,) + data.shape[-3:])[:, : plan.real_nnzt]
    out = jnp.zeros((d.shape[0], plan.n_vrows, plan.p_max, bn, bk), d.dtype)
    out = out.at[:, jnp.asarray(plan.vrow), jnp.asarray(plan.slot)].set(d)
    return out.reshape(lead + (plan.n_vrows, plan.p_max, bn, bk))


def unpack_plan_data(plan: RowPackPlan, data_rp) -> jax.Array:
    """Inverse re-layout: (..., V, P, bn, bk) -> (..., real_nnzt, bn, bk)."""
    data_rp = jnp.asarray(data_rp)
    return data_rp[..., jnp.asarray(plan.vrow), jnp.asarray(plan.slot), :, :]


# --------------------------------------------------------------------------
# the differentiable plan-backed op
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def plan_linear(x, data_rp, plan: RowPackPlan):
    """Y(M, N) = X(M, K) @ W^T with W given as a plan + row-grouped values.

    The per-call path is pattern-free compute: one gather of ``x`` at static
    indices, one batched matmul, and a segment-sum only when the plan
    spilled rows. Differentiable in ``x`` and ``data_rp`` (padding-slot
    gradients are exactly zero)."""
    return _plan_fwd_impl(x, data_rp, plan)


def _gather_x(x, plan: RowPackPlan):
    m = x.shape[0]
    bk = plan.tile[1]
    return x.reshape(m, plan.shape[1] // bk, bk)[:, jnp.asarray(plan.col_idx)]


def _plan_fwd_impl(x, data_rp, plan):
    m = x.shape[0]
    xg = _gather_x(x, plan)                               # (M, V, P, bk)
    y = jnp.einsum("mvpk,vpnk->vmn", xg, data_rp,
                   preferred_element_type=jnp.float32)    # (V, M, bn)
    if plan.spilled:
        y = jax.ops.segment_sum(y, jnp.asarray(plan.row_of_vrow),
                                num_segments=plan.n_brows)  # (R, M, bn)
    return y.transpose(1, 0, 2).reshape(m, plan.shape[0]).astype(x.dtype)


def _plan_fwd(x, data_rp, plan):
    return _plan_fwd_impl(x, data_rp, plan), (x, data_rp)


def _plan_bwd(plan, res, dy):
    x, data_rp = res
    m = x.shape[0]
    bn, bk = plan.tile
    dy_v = dy.reshape(m, plan.n_brows, bn)
    if plan.spilled:
        dy_v = dy_v[:, jnp.asarray(plan.row_of_vrow)]     # (M, V, bn)
    xg = _gather_x(x, plan)
    ddata = jnp.einsum("mvn,mvpk->vpnk", dy_v, xg,
                       preferred_element_type=jnp.float32)
    ddata = ddata * jnp.asarray(plan.slot_mask)[:, :, None, None].astype(
        ddata.dtype)
    dxg = jnp.einsum("mvn,vpnk->mvpk", dy_v, data_rp,
                     preferred_element_type=jnp.float32)
    dx = jnp.zeros((m, plan.shape[1] // bk, bk), dxg.dtype)
    dx = dx.at[:, jnp.asarray(plan.col_idx)].add(dxg)
    return (dx.reshape(m, plan.shape[1]).astype(x.dtype),
            ddata.astype(data_rp.dtype))


plan_linear.defvjp(_plan_fwd, _plan_bwd)


def plan_matmul(x: jax.Array, data_rp: jax.Array, plan: RowPackPlan):
    """Batched-x entry point: x (..., K) -> (..., N)."""
    lead = x.shape[:-1]
    y = plan_linear(x.reshape(-1, x.shape[-1]), data_rp, plan)
    return y.reshape(*lead, plan.shape[0])


# --------------------------------------------------------------------------
# compiled Pallas backend: the plan's spill schedule drives the kernel grid
# --------------------------------------------------------------------------
#
# plan_linear composes the row-grouped layout out of XLA ops (gather /
# batched matmul / segment-sum). plan_linear_pallas hands the SAME layout to
# a Pallas kernel (bsr_matmul.plan_dds): the (V, P, bn, bk) values are
# streamed in place -- the scalar-prefetched schedule below picks one
# (vrow, slot) tile per grid step -- and because tiles are visited in output-
# row order, spill vrows accumulate into the same VMEM scratch as their home
# row and the segment-sum disappears into the row-change write.

@functools.lru_cache(maxsize=None)
def plan_kernel_sequence(plan: RowPackPlan):
    """Forward tile visitation schedule for the plan-consuming kernel.

    Real tiles stably sorted by owning output block row, so home and spill
    tiles of one row are consecutive (one accumulator lifetime per row).
    Returns ``(row_seq, col_seq, vrow_seq, slot_seq)`` int32 numpy arrays;
    ``row_seq`` carries the usual write-on-row-change sentinel. Cached per
    plan fingerprint (the plan hashes by it) -- host work runs once.
    """
    vrow = np.asarray(plan.vrow, np.int64)
    slot = np.asarray(plan.slot, np.int64)
    t_row = np.asarray(plan.row_of_vrow, np.int64)[vrow]
    # pack_bsr guarantees >= 1 real tile per block row, which build_plan
    # preserves -- the write-on-row-change protocol needs full coverage
    assert np.array_equal(np.unique(t_row), np.arange(plan.n_brows)), \
        "plan does not cover every output block row"
    order = np.argsort(t_row, kind="stable")
    row_seq = np.concatenate([t_row[order], [plan.n_brows]]).astype(np.int32)
    col_seq = np.asarray(plan.col_idx, np.int64)[vrow, slot][order]
    return (row_seq, col_seq.astype(np.int32),
            vrow[order].astype(np.int32), slot[order].astype(np.int32))


@functools.lru_cache(maxsize=None)
def plan_t_sequence(plan: RowPackPlan):
    """Transposed schedule (tiles sorted by block column) for dX = dY @ W.

    Returns ``(t_row_seq, t_col_seq, t_flat)`` where ``t_flat`` indexes the
    flattened (V*P, bn, bk) values (gathered + transposed per call, like
    the KernelBSR dds_t path)."""
    vrow = np.asarray(plan.vrow, np.int64)
    slot = np.asarray(plan.slot, np.int64)
    t_row = np.asarray(plan.row_of_vrow, np.int64)[vrow]
    t_col = np.asarray(plan.col_idx, np.int64)[vrow, slot]
    assert np.array_equal(np.unique(t_col), np.arange(plan.n_bcols)), \
        "plan does not cover every input block column"
    order = np.lexsort((t_row, t_col))
    t_row_seq = np.concatenate(
        [t_col[order], [plan.n_bcols]]).astype(np.int32)
    t_col_seq = t_row[order].astype(np.int32)
    t_flat = (vrow[order] * plan.p_max + slot[order]).astype(np.int32)
    return t_row_seq, t_col_seq, t_flat


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def plan_linear_pallas(x, data_rp, plan: RowPackPlan):
    """Y(M, N) = X(M, K) @ W^T via the compiled plan-consuming Pallas kernel.

    Same layout contract as :func:`plan_linear` (row-grouped (V, P, bn, bk)
    values), same gradients (padding-slot grads exactly zero); the execution
    is one Pallas grid over the spill schedule instead of gather + einsum +
    segment-sum. Interpret mode (off-TPU) is the correctness oracle.
    """
    return _plan_pallas_fwd_impl(x, data_rp, plan)


def _plan_pallas_fwd_impl(x, data_rp, plan, bias=None, act=None):
    from repro.kernels.bsr_matmul import plan_dds
    return plan_dds(x, data_rp, plan_kernel_sequence(plan),
                    n=plan.shape[0], tile=plan.tile, bias=bias, act=act)


def _plan_pallas_fwd(x, data_rp, plan):
    return _plan_pallas_fwd_impl(x, data_rp, plan), (x, data_rp)


def _plan_pallas_bwd(plan, res, dy):
    from repro.kernels.bsr_matmul import plan_dds_t, plan_sddmm
    x, data_rp = res
    dx = plan_dds_t(dy, data_rp, plan_t_sequence(plan),
                    k=plan.shape[1], tile=plan.tile)
    seq = plan_kernel_sequence(plan)
    g_seq = plan_sddmm(dy, x, seq, tile=plan.tile, out_dtype=jnp.float32)
    # scatter schedule-ordered tile grads back into the row-grouped layout;
    # untouched (padding) slots stay exactly zero, matching slot_mask
    ddata = jnp.zeros(data_rp.shape, jnp.float32)
    ddata = ddata.at[jnp.asarray(seq[2]), jnp.asarray(seq[3])].set(g_seq)
    return dx.astype(x.dtype), ddata.astype(data_rp.dtype)


plan_linear_pallas.defvjp(_plan_pallas_fwd, _plan_pallas_bwd)


def plan_fused_linear(x, data_rp, plan: RowPackPlan, *, bias=None,
                      act: str | None = None):
    """Forward-only fused epilogue entry: bias add + activation ('relu' /
    'gelu' / 'silu') folded into the kernel's row-change write -- the
    serving-path shape of the op (no extra HBM round-trip for the
    activation between wi and wo)."""
    return _plan_pallas_fwd_impl(x, data_rp, plan, bias=bias, act=act)


def plan_matmul_pallas(x: jax.Array, data_rp: jax.Array, plan: RowPackPlan):
    """Batched-x entry point for the Pallas plan backend."""
    lead = x.shape[:-1]
    y = plan_linear_pallas(x.reshape(-1, x.shape[-1]), data_rp, plan)
    return y.reshape(*lead, plan.shape[0])


@dataclasses.dataclass(frozen=True, eq=False)
class PlanChoice:
    """A RowPackPlan pinned to a specific plan-consuming execution backend.

    ``backend='plan_pallas'`` routes models/common.linear through
    :func:`plan_linear_pallas`; the wrapper (rather than a bare plan) keeps
    the choice serializable and the pattern key distinct from the XLA plan
    path, mirroring autotune.BackendChoice for flat KernelBSR packs.
    """

    plan: RowPackPlan
    backend: str = "plan_pallas"

    @property
    def shape(self) -> Tuple[int, int]:
        return self.plan.shape

    @property
    def tile(self) -> Tuple[int, int]:
        return self.plan.tile

    @property
    def density(self) -> float:
        return self.plan.density

    @property
    def fingerprint(self) -> bytes:
        return (b"plan_choice:" + self.backend.encode() + b":"
                + self.plan.fingerprint)

    def __hash__(self):
        return hash(self.fingerprint)

    def __eq__(self, other):
        return (isinstance(other, PlanChoice)
                and self.fingerprint == other.fingerprint)


# --------------------------------------------------------------------------
# quantized packs: int8/fp8 block values, one fp32 scale per block
# --------------------------------------------------------------------------
#
# The BSR block is the natural quantization unit (Intel's sparse CPU
# accelerator, arxiv 2306.16601): one scale per (bn, bk) tile keeps the
# dequant inside the block matmul, so the XLA path folds it into the
# gathered activations (xg * scale before the einsum -- exactly equivalent,
# fp32 weight values never land in the params tree) and the Pallas path
# multiplies the accumulator contribution by the scalar-prefetched scale.
# Skinny tiles (bn*bk below _QUANT_BLOCK_MIN_ELEMS, e.g. the paper's 32x1
# column blocks) would spend one fp32 scale per <=32 values; they fall back
# to one scale per virtual row (the row group), bounding scale overhead.

QUANT_DTYPES = ("int8", "fp8")
#: per-block scales need bn*bk elements to amortize their 4 bytes; below
#: this the scale granularity falls back to one per row group (vrow)
_QUANT_BLOCK_MIN_ELEMS = 128
_FP8_MAX = 448.0                       # float8_e4m3fn finite max


def fp8_dtype():
    """jnp.float8_e4m3fn when this jax build has float8, else None."""
    return getattr(jnp, "float8_e4m3fn", None)


def quant_granularity(tile: Tuple[int, int]) -> str:
    """'block' (one scale per (bn, bk) tile) for tiles that amortize the
    fp32 scale; 'row' (one per virtual row group) for skinny tiles."""
    return "block" if tile[0] * tile[1] >= _QUANT_BLOCK_MIN_ELEMS else "row"


def _qparams(qdtype: str):
    if qdtype == "int8":
        return 127.0, jnp.int8
    if qdtype == "fp8":
        ft = fp8_dtype()
        if ft is None:
            raise NotImplementedError(
                "pack_quant='fp8' needs a jax build with float8_e4m3fn; "
                "this one has none (use 'int8')")
        return _FP8_MAX, ft
    raise ValueError(f"qdtype={qdtype!r} not in {QUANT_DTYPES}")


def quantize_plan_values(data_rp, qdtype: str, granularity: str):
    """Row-grouped values (..., V, P, bn, bk) -> (qvalues, scales).

    Symmetric absmax quantization: ``scales`` is (..., V, P) fp32 for
    'block' granularity, (..., V, 1) for 'row' (the trailing 1 broadcasts
    over slots, and gives the Pallas kernel a static slot-0 index map).
    All-zero groups (pruned padding slots) get scale 1.0 so dequant stays
    exact zero."""
    d = jnp.asarray(data_rp, jnp.float32)
    if granularity == "block":
        amax = jnp.max(jnp.abs(d), axis=(-2, -1))          # (..., V, P)
    elif granularity == "row":
        amax = jnp.max(jnp.abs(d), axis=(-3, -2, -1))[..., None]
    else:
        raise ValueError(f"granularity={granularity!r}")
    qmax, qt = _qparams(qdtype)
    scales = jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)
    s = scales[..., None, None]                # broadcast over (bn, bk)
    if qdtype == "int8":
        q = jnp.clip(jnp.round(d / s), -qmax, qmax).astype(qt)
    else:
        q = (d / s).astype(qt)
    return q, scales


def dequantize_plan_values(qvalues, scales) -> jax.Array:
    """(qvalues, scales) -> fp32 row-grouped values (the export-time
    round-trip check and the serialize-compat path; serving never calls
    this -- dequant stays fused in the matmul)."""
    q = jnp.asarray(qvalues).astype(jnp.float32)
    return q * jnp.asarray(scales)[..., None, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def plan_q_linear(x, qvalues, scales, plan: RowPackPlan):
    """Y(M, N) = X(M, K) @ dequant(Q)^T with the dequant fused.

    Scaling the *gathered activations* (xg[m, v, p, :] * scales[v, p]) is
    exactly the per-block weight dequant re-associated onto the small
    operand, so the einsum contracts int8/fp8 values directly (XLA fuses
    the widening convert into the contraction) and a full fp32 weight
    tensor never materializes. Differentiable in ``x`` (serving + probe
    path); quantized values are constants, their grad is zero."""
    return _plan_q_fwd_impl(x, qvalues, scales, plan)


def _scale_xg(xg, scales):
    # xg (M, V, P, bk); scales (V, P) or (V, 1) -> broadcast over bk (and
    # over slots for row granularity)
    return xg.astype(jnp.float32) * scales[..., None]


def _plan_q_fwd_impl(x, qvalues, scales, plan):
    m = x.shape[0]
    xs = _scale_xg(_gather_x(x, plan), scales)            # (M, V, P, bk)
    y = jnp.einsum("mvpk,vpnk->vmn", xs, qvalues.astype(jnp.float32),
                   preferred_element_type=jnp.float32)    # (V, M, bn)
    if plan.spilled:
        y = jax.ops.segment_sum(y, jnp.asarray(plan.row_of_vrow),
                                num_segments=plan.n_brows)
    return y.transpose(1, 0, 2).reshape(m, plan.shape[0]).astype(x.dtype)


def _plan_q_fwd(x, qvalues, scales, plan):
    return _plan_q_fwd_impl(x, qvalues, scales, plan), (x, qvalues, scales)


def _plan_q_bwd(plan, res, dy):
    x, qvalues, scales = res
    m = x.shape[0]
    bn, bk = plan.tile
    dy_v = dy.reshape(m, plan.n_brows, bn)
    if plan.spilled:
        dy_v = dy_v[:, jnp.asarray(plan.row_of_vrow)]     # (M, V, bn)
    dxg = jnp.einsum("mvn,vpnk->mvpk", dy_v, qvalues.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    dxg = _scale_xg(dxg, scales)       # re-associate the dequant onto dX
    dx = jnp.zeros((m, plan.shape[1] // bk, bk), dxg.dtype)
    dx = dx.at[:, jnp.asarray(plan.col_idx)].add(dxg)
    return (dx.reshape(m, plan.shape[1]).astype(x.dtype),
            jnp.zeros_like(qvalues), jnp.zeros_like(scales))


plan_q_linear.defvjp(_plan_q_fwd, _plan_q_bwd)


def plan_q_matmul(x: jax.Array, qvalues, scales, plan: RowPackPlan):
    """Batched-x entry point for the dequant-fused XLA plan backend."""
    lead = x.shape[:-1]
    y = plan_q_linear(x.reshape(-1, x.shape[-1]), qvalues, scales, plan)
    return y.reshape(*lead, plan.shape[0])


def plan_q_linear_pallas(x, qvalues, scales, plan: RowPackPlan, *,
                         bias=None, act: str | None = None):
    """Dequant-fused plan matmul via the compiled Pallas kernel: the
    per-block scale rides the scalar-prefetched schedule and multiplies
    the accumulator contribution in place (bsr_matmul.plan_dds_q), with
    the same fused bias/act epilogue as :func:`plan_fused_linear`.
    Forward-only (serving path)."""
    from repro.kernels.bsr_matmul import plan_dds_q
    granularity = "row" if scales.shape[-1] == 1 else "block"
    return plan_dds_q(x, qvalues, scales, plan_kernel_sequence(plan),
                      n=plan.shape[0], tile=plan.tile,
                      granularity=granularity, bias=bias, act=act)


def plan_q_matmul_pallas(x: jax.Array, qvalues, scales,
                         plan: RowPackPlan):
    """Batched-x entry point for the dequant-fused Pallas plan backend."""
    lead = x.shape[:-1]
    y = plan_q_linear_pallas(x.reshape(-1, x.shape[-1]), qvalues, scales,
                             plan)
    return y.reshape(*lead, plan.shape[0])


@dataclasses.dataclass(frozen=True, eq=False)
class QuantPlan:
    """A RowPackPlan whose values are stored quantized (int8/fp8 + fp32
    scales) and served through the dequant-fused plan matmul.

    Wraps the (possibly Sharded) plan rather than subclassing it: the
    pattern, spill schedule and shard layout are untouched -- only the
    value storage and the dispatch change. The params-tree entry for a
    QuantPlan pack is ``{"w": qvalues, "scale": scales}`` (dtype-cast and
    byte accounting treat it specially; serving/servable.py).

    ``backend`` pins the execution path: 'plan' = the XLA composition
    (:func:`plan_q_matmul`), 'plan_pallas' = the compiled kernel
    (:func:`plan_q_matmul_pallas`).
    """

    plan: RowPackPlan
    qdtype: str = "int8"               # 'int8' | 'fp8'
    granularity: str = "block"         # 'block' (V, P) | 'row' (V, 1)
    backend: str = "plan"              # 'plan' | 'plan_pallas'

    @property
    def shape(self) -> Tuple[int, int]:
        return self.plan.shape

    @property
    def tile(self) -> Tuple[int, int]:
        return self.plan.tile

    @property
    def density(self) -> float:
        return self.plan.density

    @property
    def real_nnzt(self) -> int:
        return self.plan.real_nnzt

    @property
    def fingerprint(self) -> bytes:
        return (b"quant:" + self.qdtype.encode() + b":"
                + self.granularity.encode() + b":" + self.backend.encode()
                + b":" + self.plan.fingerprint)

    def with_mesh(self, mesh) -> "QuantPlan":
        """Mesh attachment passthrough for ShardedPlan inners."""
        if isinstance(self.plan, ShardedPlan):
            return dataclasses.replace(self, plan=self.plan.with_mesh(mesh))
        return self

    def __hash__(self):
        return hash(self.fingerprint)

    def __eq__(self, other):
        return (isinstance(other, QuantPlan)
                and self.fingerprint == other.fingerprint)


def quantize_for_plan(plan: RowPackPlan, data, qdtype: str, *,
                      backend: str = "plan"):
    """Packed tile values (..., nnzt, bn, bk) -> (QuantPlan, params dict).

    The export-time quantize pass: row-group the values (pack_plan_data),
    pick the scale granularity from the tile, quantize. Returns the
    QuantPlan wrapper and its ``{"w", "scale"}`` params entry."""
    data_rp = pack_plan_data(plan, data)
    granularity = quant_granularity(plan.tile)
    q, s = quantize_plan_values(data_rp, qdtype, granularity)
    qp = QuantPlan(plan, qdtype=qdtype, granularity=granularity,
                   backend=backend)
    return qp, {"w": q, "scale": s}
