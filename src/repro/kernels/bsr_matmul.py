"""Pallas TPU kernels for block-sparse linear layers (the paper's TVM+ ops).

TPU adaptation of the paper's BSR operators: the *sparsity* blocks chosen by
the regularizer (e.g. 32x1) are aggregated on the host into *kernel tiles*
sized for the MXU/VMEM (default 128x128; a tile is stored iff it contains any
nonzero sparsity block). The kernels then skip whole tiles:

  * ``dds``    -- Y(M,N) = X(M,K) @ W^T, W an (N,K) tile-BSR. Scalar-prefetched
                  ``row_id/col_id`` (SMEM) drive the BlockSpec index maps, so
                  only stored tiles are DMA'd into VMEM and MXU time scales
                  with density. This is the serving hot path.
  * ``sddmm``  -- dW.data[j] = dY[:,row_j]^T @ X[:,col_j]: gradient w.r.t.
                  stored tiles only (sparse training backward).
  * ``masked`` -- dense-layout matmul that skips MXU work on zero tiles via a
                  prefetched tile mask, but still pays the full weight DMA.
                  It is the "sparsity without format support" middle ground --
                  the measurable analogue of the paper's negative control
                  (stock TVM: sparse model, no BSR support, no win).

All kernels accumulate in fp32 VMEM scratch and are validated against
ref.py oracles in interpret mode (CPU) across shape/dtype sweeps.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bsr import BSR, bsr_to_dense


def pallas_interpret_default(interpret: bool | None = None) -> bool:
    """Resolve a Pallas entry's ``interpret`` flag; ``None`` (every entry's
    default) lets the platform decide: kernels compile on TPU, everywhere
    else interpret mode is the correctness oracle (docs/PERF.md: orders of
    magnitude slower)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def pallas_tile_supported(tile: Tuple[int, int]) -> bool:
    """Whether a compiled (non-interpret) kernel can take ``(bn, bk)`` tiles:
    every block it streams -- x (bm, bk), weights (bn, bk), output and bias
    (bm, bn) -- must obey the TPU (8, 128) tiling, so both tile dims must be
    lane multiples."""
    bn, bk = tile
    return bn % 128 == 0 and bk % 128 == 0


def check_pallas_tile(tile: Tuple[int, int],
                      interpret: bool | None = None) -> None:
    """Raise a ValueError for a tile the compiled kernels cannot take (the
    Mosaic lowering would otherwise refuse it with a block-shape error deep
    inside the first call). Interpret mode takes any tile."""
    if (not pallas_interpret_default(interpret)
            and not pallas_tile_supported(tile)):
        raise ValueError(
            f"tile {tuple(tile)} cannot run through a compiled Pallas "
            f"backend on {jax.default_backend()}: both tile dims must be "
            f"multiples of 128; use the XLA 'plan' backend or a 128-aligned "
            f"tile")


# --------------------------------------------------------------------------
# Host-side packing
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelBSR:
    """Tile-granular BSR pack for the Pallas kernels.

    The pattern arrays are *host numpy* and are treated as static: every
    distinct pattern is its own specialization, which is exactly the TVM
    task-buffer model (see core/pattern_reuse.py for the reuse cache).

    row_id has one sentinel entry appended (== n_brows) so the kernel can
    detect the last tile of each block row without branching on bounds.
    """

    data: jax.Array          # (nnzt, bn, bk) stored tile values
    row_id: np.ndarray       # (nnzt + 1,) int32, sorted, sentinel-terminated
    col_id: np.ndarray       # (nnzt,) int32
    t_perm: np.ndarray       # (nnzt,) permutation sorting tiles by (col, row)
    real_nnzt: int           # stored tiles that are not padding
    shape: Tuple[int, int]   # (N, K)
    tile: Tuple[int, int]    # (bn, bk)

    @property
    def nnzt(self) -> int:
        return int(self.col_id.shape[0])

    @property
    def n_brows(self) -> int:
        return self.shape[0] // self.tile[0]

    @property
    def n_bcols(self) -> int:
        return self.shape[1] // self.tile[1]

    @property
    def density(self) -> float:
        return self.real_nnzt / max(1, self.n_brows * self.n_bcols)

    def pad_mask(self) -> np.ndarray:
        m = np.zeros((self.nnzt,), bool)
        m[: self.real_nnzt] = True
        return m

    # transpose-pattern views (for dX = dY @ W)
    def t_row_id(self) -> np.ndarray:
        t = self.col_id[self.t_perm]
        return np.concatenate([t, [self.n_bcols]]).astype(np.int32)

    def t_col_id(self) -> np.ndarray:
        return self.row_id[:-1][self.t_perm].astype(np.int32)


def pack_bsr(w, tile: Tuple[int, int], nnzt: int | None = None) -> KernelBSR:
    """Pack a dense (or core.BSR) weight into tile-granular KernelBSR.

    Guarantees every block row stores >= 1 tile (zero-valued if the row is
    empty) so the kernel's write-on-row-change protocol covers all outputs.
    Runs on host; this is the offline "model packing" step, mirroring TVM's
    relay transformation of dense weights into BSR params.
    """
    if isinstance(w, BSR):
        w = np.asarray(jax.device_get(bsr_to_dense(w)))
    w = np.asarray(w)
    n, k = w.shape
    bn, bk = tile
    assert n % bn == 0 and k % bk == 0, (w.shape, tile)
    nbr, nbc = n // bn, k // bk

    blocks = w.reshape(nbr, bn, nbc, bk).transpose(0, 2, 1, 3)
    mask = np.any(blocks != 0, axis=(2, 3))
    # Every row AND column must store >= 1 tile (zero-valued if needed) so the
    # write-on-row-change protocol covers all outputs in both the forward and
    # the transposed (dds_t) orientation.
    for r in np.nonzero(~mask.any(axis=1))[0]:
        mask[r, 0] = True
    for c in np.nonzero(~mask.any(axis=0))[0]:
        mask[0, c] = True
    rows, cols = np.nonzero(mask)
    real = len(rows)
    if nnzt is None:
        nnzt = real
    if real > nnzt:
        raise ValueError(f"nnzt={nnzt} < required tiles {real}")

    data = np.zeros((nnzt, bn, bk), dtype=w.dtype)
    data[:real] = blocks[rows, cols]
    row_id = np.full((nnzt + 1,), nbr, dtype=np.int32)
    row_id[:real] = rows
    row_id[real:nnzt] = nbr - 1        # padding tiles live in the last row
    col_id = np.zeros((nnzt,), dtype=np.int32)
    col_id[:real] = cols

    t_perm = np.lexsort((row_id[:nnzt], col_id)).astype(np.int32)
    return KernelBSR(jnp.asarray(data), row_id, col_id, t_perm,
                     real, (n, k), tile)


# --------------------------------------------------------------------------
# DDS: Y = X @ W^T   (dense = dense x sparse)
# --------------------------------------------------------------------------

def _dds_kernel(row_ref, col_ref, x_ref, w_ref, o_ref, acc_ref):
    j = pl.program_id(1)
    first = (j == 0) | (row_ref[j] != row_ref[jnp.maximum(j - 1, 0)])

    @pl.when(first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(row_ref[j + 1] != row_ref[j])
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("pack_static", "bm", "interpret"))
def _dds_call(x, data, row_id, col_id, *, pack_static, bm, interpret):
    n, k = pack_static[0]
    bn, bk = pack_static[1]
    nnzt = int(col_id.shape[0])
    m = x.shape[0]
    grid = (m // bm, nnzt)
    return pl.pallas_call(
        _dds_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, row, col: (i, col[j])),
                pl.BlockSpec((1, bn, bk), lambda i, j, row, col: (j, 0, 0)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, row, col: (i, row[j])),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(row_id, col_id, x, data)


def dds(x: jax.Array, w: KernelBSR, *, bm: int = 128,
        interpret: bool | None = None) -> jax.Array:
    """Y(M, N) = X(M, K) @ W^T with tile skipping. Pads M to bm internally."""
    m, k = x.shape
    assert k == w.shape[1], (x.shape, w.shape)
    bm = min(bm, _ceil_mult(m, 8))
    pad = (-m) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    y = _dds_call(x, w.data, jnp.asarray(w.row_id), jnp.asarray(w.col_id),
                  pack_static=(w.shape, w.tile), bm=bm,
                  interpret=pallas_interpret_default(interpret))
    return y[:m] if pad else y


def dds_t(dy: jax.Array, w: KernelBSR, *, bm: int = 128,
          interpret: bool | None = None) -> jax.Array:
    """dX(M, K) = dY(M, N) @ W, reusing the DDS kernel on the transposed
    pattern (tiles re-sorted by column on host at pack time)."""
    t_data = jnp.transpose(w.data[jnp.asarray(w.t_perm)], (0, 2, 1))
    m = dy.shape[0]
    bm = min(bm, _ceil_mult(m, 8))
    pad = (-m) % bm
    if pad:
        dy = jnp.pad(dy, ((0, pad), (0, 0)))
    x = _dds_call(dy, t_data, jnp.asarray(w.t_row_id()),
                  jnp.asarray(w.t_col_id()),
                  pack_static=((w.shape[1], w.shape[0]),
                               (w.tile[1], w.tile[0])),
                  bm=bm, interpret=pallas_interpret_default(interpret))
    return x[:m] if pad else x


# --------------------------------------------------------------------------
# SDDMM: dW.data[j] = dY[:, row_j]^T @ X[:, col_j]
# --------------------------------------------------------------------------

def _sddmm_kernel(row_ref, col_ref, dy_ref, x_ref, o_ref, acc_ref, *, num_m):
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        dy_ref[...], x_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(mi == num_m - 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("pack_static", "bm", "interpret"))
def _sddmm_call(dy, x, row_id, col_id, *, pack_static, bm, interpret):
    (n, k), (bn, bk), out_dtype = pack_static
    nnzt = int(col_id.shape[0])
    m = x.shape[0]
    num_m = m // bm
    grid = (nnzt, num_m)
    return pl.pallas_call(
        functools.partial(_sddmm_kernel, num_m=num_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bn), lambda j, mi, row, col: (mi, row[j])),
                pl.BlockSpec((bm, bk), lambda j, mi, row, col: (mi, col[j])),
            ],
            out_specs=pl.BlockSpec((1, bn, bk), lambda j, mi, row, col: (j, 0, 0)),
            scratch_shapes=[pltpu.VMEM((bn, bk), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((nnzt, bn, bk), out_dtype),
        interpret=interpret,
    )(row_id, col_id, dy, x)


def sddmm(dy: jax.Array, x: jax.Array, w: KernelBSR, *, bm: int = 128,
          interpret: bool | None = None) -> jax.Array:
    """Gradient w.r.t. stored tiles. Returns (nnzt, bn, bk); padding tiles
    receive garbage and are zeroed here (they must stay dead)."""
    m = x.shape[0]
    bm = min(bm, _ceil_mult(m, 8))
    pad = (-m) % bm
    if pad:
        dy = jnp.pad(dy, ((0, pad), (0, 0)))
        x = jnp.pad(x, ((0, pad), (0, 0)))
    g = _sddmm_call(dy, x, jnp.asarray(w.row_id), jnp.asarray(w.col_id),
                    pack_static=(w.shape, w.tile, w.data.dtype),
                    bm=bm, interpret=pallas_interpret_default(interpret))
    return g * jnp.asarray(w.pad_mask())[:, None, None].astype(g.dtype)


# --------------------------------------------------------------------------
# Masked dense-layout matmul (negative-control arm)
# --------------------------------------------------------------------------

def _masked_kernel(mask_ref, x_ref, w_ref, o_ref, acc_ref, *, nk):
    ni, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(mask_ref[ni * nk + ki] != 0)
    def _():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "bm", "interpret"))
def _masked_call(x, w, tile_mask, *, tile, bm, interpret):
    m, k = x.shape
    n = w.shape[0]
    bn, bk = tile
    nn, nk = n // bn, k // bk
    grid = (m // bm, nn, nk)
    return pl.pallas_call(
        functools.partial(_masked_kernel, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, ni, ki, mask: (i, ki)),
                pl.BlockSpec((bn, bk), lambda i, ni, ki, mask: (ni, ki)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, ni, ki, mask: (i, ni)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(tile_mask.reshape(-1).astype(jnp.int32), x, w)


def masked_matmul(x: jax.Array, w_dense: jax.Array, tile_mask: jax.Array,
                  *, tile: Tuple[int, int] = (128, 128), bm: int = 128,
                  interpret: bool | None = None) -> jax.Array:
    """Y = X @ W^T skipping MXU work on zero tiles; W stays dense in HBM.

    Saves compute but NOT memory traffic -- quantifying why format support
    (BSR) is required for real wins, the paper's negative-control finding.
    """
    m = x.shape[0]
    bm = min(bm, _ceil_mult(m, 8))
    pad = (-m) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    y = _masked_call(x, w_dense, tile_mask, tile=tile, bm=bm,
                     interpret=pallas_interpret_default(interpret))
    return y[:m] if pad else y


def _ceil_mult(v: int, m: int) -> int:
    return max(m, ((v + m - 1) // m) * m)


# --------------------------------------------------------------------------
# Plan-consuming DDS: the RowPackPlan layout, streamed per row group
# --------------------------------------------------------------------------
#
# The kernels above read the flat KernelBSR (nnzt, bn, bk) stream. The
# serving layout, however, is the RowPackPlan's row-grouped (V, P, bn, bk)
# pack (exec_plan.py): home virtual rows 0..R-1 plus appended spill rows,
# each holding up to P tiles with per-slot column ids. ``plan_dds`` consumes
# that pack *directly* -- no re-layout, no segment-sum epilogue: the block
# loop follows the precomputed spill schedule (tiles stably sorted by output
# row on the host, see exec_plan.plan_kernel_sequence), so home and spill
# tiles of one output row are visited consecutively and accumulate in the
# same VMEM scratch; the row-change write doubles as the spill reduction.
# A bias add + activation can be fused into that final write (epilogue).

def _act_epilogue(y, act):
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "gelu":
        return jax.nn.gelu(y)
    if act == "silu":
        return y * jax.nn.sigmoid(y)
    assert act is None, act
    return y


def _plan_dds_kernel(row_ref, col_ref, vrow_ref, slot_ref, x_ref, w_ref,
                     b_ref, o_ref, acc_ref, *, act, bias):
    j = pl.program_id(1)
    first = (j == 0) | (row_ref[j] != row_ref[jnp.maximum(j - 1, 0)])

    @pl.when(first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[0, 0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(row_ref[j + 1] != row_ref[j])
    def _():
        y = acc_ref[...]
        if bias:
            y = y + b_ref[...].astype(jnp.float32)
        o_ref[...] = _act_epilogue(y, act).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "tile", "bm", "act",
                                             "bias", "interpret"))
def _plan_dds_call(x, data_rp, b, row_seq, col_seq, vrow_seq, slot_seq, *,
                   n, tile, bm, act, bias, interpret):
    bn, bk = tile
    nnzt = int(col_seq.shape[0])
    m = x.shape[0]
    grid = (m // bm, nnzt)
    return pl.pallas_call(
        functools.partial(_plan_dds_kernel, act=act, bias=bias),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk),
                             lambda i, j, row, col, vr, sl: (i, col[j])),
                # stream the (V, P, bn, bk) pack in place: the scalar-
                # prefetched schedule picks (virtual row, slot) per step
                pl.BlockSpec((1, 1, bn, bk),
                             lambda i, j, row, col, vr, sl:
                             (vr[j], sl[j], 0, 0)),
                pl.BlockSpec((1, bn),
                             lambda i, j, row, col, vr, sl: (0, row[j])),
            ],
            out_specs=pl.BlockSpec(
                (bm, bn), lambda i, j, row, col, vr, sl: (i, row[j])),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(row_seq, col_seq, vrow_seq, slot_seq, x, data_rp, b)


def plan_dds(x: jax.Array, data_rp: jax.Array, schedule, *, n: int,
             tile: Tuple[int, int], bias: jax.Array | None = None,
             act: str | None = None, bm: int = 128,
             interpret: bool | None = None) -> jax.Array:
    """Y(M, N) = X(M, K) @ W^T from the row-grouped (V, P, bn, bk) pack.

    ``schedule`` is the (row_seq, col_seq, vrow_seq, slot_seq) tuple from
    exec_plan.plan_kernel_sequence: real tiles stably sorted by output block
    row, row_seq sentinel-terminated. ``bias`` (N,) and ``act`` fuse into
    the row-change write.
    """
    check_pallas_tile(tile, interpret)
    m = x.shape[0]
    row_seq, col_seq, vrow_seq, slot_seq = schedule
    bm = min(bm, _ceil_mult(m, 8))
    pad = (-m) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    has_bias = bias is not None
    b = (bias.reshape(1, n) if has_bias
         else jnp.zeros((1, n), x.dtype))
    y = _plan_dds_call(x, data_rp, b, jnp.asarray(row_seq),
                       jnp.asarray(col_seq), jnp.asarray(vrow_seq),
                       jnp.asarray(slot_seq), n=n, tile=tile, bm=bm,
                       act=act, bias=has_bias,
                       interpret=pallas_interpret_default(interpret))
    return y[:m] if pad else y


def _plan_dds_q_kernel(row_ref, col_ref, vrow_ref, slot_ref, s_ref, x_ref,
                       w_ref, b_ref, o_ref, acc_ref, *, act, bias):
    # same schedule/accumulator protocol as _plan_dds_kernel; the block
    # values arrive int8/fp8 and the per-step scale rides the scalar-
    # prefetched schedule (SMEM) -- dequant is one scalar multiply on the
    # tile's contribution, inside the accumulation, so fp32 weight values
    # never exist outside VMEM.
    j = pl.program_id(1)
    first = (j == 0) | (row_ref[j] != row_ref[jnp.maximum(j - 1, 0)])

    @pl.when(first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += s_ref[j] * jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[0, 0].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(row_ref[j + 1] != row_ref[j])
    def _():
        y = acc_ref[...]
        if bias:
            y = y + b_ref[...].astype(jnp.float32)
        o_ref[...] = _act_epilogue(y, act).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "tile", "bm",
                                             "granularity", "act", "bias",
                                             "interpret"))
def _plan_dds_q_call(x, qvalues, scales, b, row_seq, col_seq, vrow_seq,
                     slot_seq, *, n, tile, bm, granularity, act, bias,
                     interpret):
    bn, bk = tile
    nnzt = int(col_seq.shape[0])
    m = x.shape[0]
    grid = (m // bm, nnzt)
    # one scale per schedule step, gathered here and prefetched to SMEM
    # with the schedule: 'block' scales are (V, P), read at (vr[j], sl[j]);
    # 'row' scales are (V, 1), shared by every slot of a vrow
    s_seq = scales[vrow_seq, slot_seq if granularity == "block" else 0]
    return pl.pallas_call(
        functools.partial(_plan_dds_q_kernel, act=act, bias=bias),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk),
                             lambda i, j, row, col, vr, sl, sc: (i, col[j])),
                pl.BlockSpec((1, 1, bn, bk),
                             lambda i, j, row, col, vr, sl, sc:
                             (vr[j], sl[j], 0, 0)),
                pl.BlockSpec((1, bn),
                             lambda i, j, row, col, vr, sl, sc: (0, row[j])),
            ],
            out_specs=pl.BlockSpec(
                (bm, bn), lambda i, j, row, col, vr, sl, sc: (i, row[j])),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(row_seq, col_seq, vrow_seq, slot_seq, s_seq.astype(jnp.float32), x,
      qvalues, b)


def plan_dds_q(x: jax.Array, qvalues: jax.Array, scales: jax.Array,
               schedule, *, n: int, tile: Tuple[int, int],
               granularity: str = "block", bias: jax.Array | None = None,
               act: str | None = None, bm: int = 128,
               interpret: bool | None = None) -> jax.Array:
    """Y(M, N) = X(M, K) @ dequant(Q)^T, dequant fused into the block loop.

    Same contract as :func:`plan_dds` with the (V, P, bn, bk) values stored
    int8/fp8 and ``scales`` (V, P) fp32 ('block' granularity) or (V, 1)
    ('row'). Each tile's partial product is scaled before it joins the VMEM
    accumulator; bias/act fuse into the row-change write as before.
    """
    check_pallas_tile(tile, interpret)
    m = x.shape[0]
    row_seq, col_seq, vrow_seq, slot_seq = schedule
    bm = min(bm, _ceil_mult(m, 8))
    pad = (-m) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    has_bias = bias is not None
    b = (bias.reshape(1, n) if has_bias
         else jnp.zeros((1, n), x.dtype))
    y = _plan_dds_q_call(x, qvalues, scales, b, jnp.asarray(row_seq),
                         jnp.asarray(col_seq), jnp.asarray(vrow_seq),
                         jnp.asarray(slot_seq), n=n, tile=tile, bm=bm,
                         granularity=granularity, act=act, bias=has_bias,
                         interpret=pallas_interpret_default(interpret))
    return y[:m] if pad else y


def plan_dds_t(dy: jax.Array, data_rp: jax.Array, t_schedule, *, k: int,
               tile: Tuple[int, int], bm: int = 128,
               interpret: bool | None = None) -> jax.Array:
    """dX(M, K) = dY(M, N) @ W on the transposed schedule (tiles sorted by
    block column); tile values are gathered+transposed per call, like dds_t.
    """
    bn, bk = tile
    t_row_seq, t_col_seq, t_flat = t_schedule
    flat = data_rp.reshape(-1, bn, bk)
    t_data = jnp.transpose(flat[jnp.asarray(t_flat)], (0, 2, 1))
    m, n = dy.shape
    bm = min(bm, _ceil_mult(m, 8))
    pad = (-m) % bm
    if pad:
        dy = jnp.pad(dy, ((0, pad), (0, 0)))
    x = _dds_call(dy, t_data, jnp.asarray(t_row_seq), jnp.asarray(t_col_seq),
                  pack_static=((k, n), (bk, bn)), bm=bm,
                  interpret=pallas_interpret_default(interpret))
    return x[:m] if pad else x


def plan_sddmm(dy: jax.Array, x: jax.Array, schedule, *,
               tile: Tuple[int, int], out_dtype, bm: int = 128,
               interpret: bool | None = None) -> jax.Array:
    """Per-tile gradient dW[j] = dY[:, row_j]^T @ X[:, col_j] over the
    schedule order. Returns (nnzt, bn, bk); all schedule tiles are real
    (the plan keeps no padding tiles in its vrow/slot lists)."""
    row_seq, col_seq, _, _ = schedule
    m = x.shape[0]
    bm = min(bm, _ceil_mult(m, 8))
    pad = (-m) % bm
    if pad:
        dy = jnp.pad(dy, ((0, pad), (0, 0)))
        x = jnp.pad(x, ((0, pad), (0, 0)))
    n = dy.shape[1]
    k = x.shape[1]
    return _sddmm_call(dy, x, jnp.asarray(row_seq), jnp.asarray(col_seq),
                       pack_static=((n, k), tile, out_dtype),
                       bm=bm, interpret=pallas_interpret_default(interpret))
