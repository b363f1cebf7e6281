"""Public ops for block-sparse linears: jit'd, differentiable, backend-dispatched.

``bsr_linear(x, data, pack)`` is the layer-facing op: custom_vjp over both the
activations and the stored tile values so sparse *training* works (gradient of
pruned blocks is exactly zero -- they stay dead).

Backends:
  * "pallas"  -- the TPU kernels of bsr_matmul.py (interpret=True off-TPU,
                 which is far too slow to serve from: CPU uses rowpack);
  * "rowpack" -- row-grouped batched matmul, the measured CPU fast path and
                 the off-TPU default (TVM+ analogue in benchmarks/table1).
                 Its static layout (fixed P = max tiles/row) is computed once
                 per pattern and cached; because ``data`` arrives in the
                 packed (nnzt, bn, bk) layout, this backend still pays one
                 scatter-to-row-groups per call;
  * "gather"  -- pure-XLA sparse path (ref.bsr_matmul_gather): one gather per
                 stored tile, O(nnzt) scattered traffic -- simple, and the
                 baseline rowpack overtook (docs/PERF.md);
  * "ref"     -- densify oracle (correctness reference, not a serving path).

``default_backend()`` picks pallas on TPU, rowpack elsewhere.

The serving-optimal path is NOT a ``bsr_linear`` backend: store weights
row-grouped offline and call ``exec_plan.plan_linear`` directly (what the
repro.serving exports do). That removes the per-call scatter too --
see docs/PERF.md for the measured ladder gather -> rowpack -> plan.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bsr import BSR
from repro.kernels import bsr_matmul as bk
from repro.kernels import exec_plan as xp
from repro.kernels import ref as kref
from repro.kernels.bsr_matmul import KernelBSR, pack_bsr


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "rowpack"


def _rowpack_layout(pack: KernelBSR):
    """Static row-grouped layout (col_idx (R, P), slot (nnzt,), P) with the
    seed semantics: fixed P = max tiles/row, padding tiles included.

    Vectorized (the seed rebuilt this with a Python loop at every trace) and
    cached per pattern fingerprint through the plan registry; the adaptive
    spill-scheduled layout lives in exec_plan.build_plan -- this fixed
    layout is kept as the measured baseline the plan path is benchmarked
    against (docs/PERF.md).
    """
    def build():
        rows = np.asarray(pack.row_id[: pack.nnzt], dtype=np.int64)
        r = pack.n_brows
        counts = np.bincount(rows, minlength=r)
        p = max(1, int(counts.max()))
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        order = np.argsort(rows, kind="stable")
        slot = np.empty(rows.shape[0], np.int64)
        slot[order] = np.arange(rows.shape[0]) - starts[rows[order]]
        col_idx = np.zeros((r, p), np.int64)
        col_idx[rows, slot] = pack.col_id
        return col_idx, slot, p

    reg = xp.default_plan_registry()
    key = ("rowpack_layout", xp.kernel_pattern_fingerprint(pack))
    return reg.cached(key, build)


def _rowpack_matmul(x, data, pack: KernelBSR):
    """Row-grouped matmul (docs/PERF.md §rowpack): instead of one gather per
    stored block (O(M * nnzt * bk) scattered traffic), group blocks by output
    row, pad to P = max blocks/row, and run ONE batched
    (R, M, P*bk) x (R, P*bk, bn) matmul. Padding blocks multiply zeros.

    The data re-layout below runs on every call because this backend's ABI
    takes ``data`` in the packed (nnzt, bn, bk) layout -- exactly the cost
    the RowPackPlan serving path moves offline.
    """
    m = x.shape[0]
    n, k = pack.shape
    bn, bk = pack.tile
    r = pack.n_brows
    col_idx, slot, p = _rowpack_layout(pack)
    rows = pack.row_id[: pack.nnzt]
    data_rp = jnp.zeros((r, p, bn, bk), data.dtype)
    data_rp = data_rp.at[jnp.asarray(rows), jnp.asarray(slot)].set(data)
    xg = x.reshape(m, k // bk, bk)[:, jnp.asarray(col_idx)]   # (M,R,P,bk)
    y = jnp.einsum("mrpk,rpnk->rmn", xg, data_rp,
                   preferred_element_type=jnp.float32)        # (R,M,bn)
    return y.transpose(1, 0, 2).reshape(m, n).astype(x.dtype)


def _core_bsr_from_pack(data, pack: KernelBSR) -> BSR:
    """View a KernelBSR (static pattern) as a core BSR (for the gather path)."""
    nbr = pack.n_brows
    counts = np.bincount(pack.row_id[:-1], minlength=nbr)
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    return BSR(data, jnp.asarray(pack.col_id), jnp.asarray(indptr),
               pack.shape, pack.tile)


# --------------------------------------------------------------------------
# differentiable primitive
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def bsr_linear(x, data, pack: KernelBSR, backend: str = "gather"):
    """Y(M, N) = X(M, K) @ W^T with W = (pack pattern, data values)."""
    return _bsr_linear_fwd_impl(x, data, pack, backend)


def _bsr_linear_fwd_impl(x, data, pack, backend):
    if backend == "pallas":
        return bk.dds(x, _with_data(pack, data))
    if backend == "rowpack":
        return _rowpack_matmul(x, data, pack)
    m = _core_bsr_from_pack(data, pack)
    if backend == "gather":
        return kref.bsr_matmul_gather(x, m)
    if backend == "ref":
        return kref.bsr_matmul_ref(x, m)
    raise ValueError(f"unknown backend {backend}")


def _bsr_linear_fwd(x, data, pack, backend):
    return _bsr_linear_fwd_impl(x, data, pack, backend), (x, data)


def _bsr_linear_bwd(pack, backend, res, dy):
    x, data = res
    if backend == "pallas":
        dx = bk.dds_t(dy, _with_data(pack, data))
        ddata = bk.sddmm(dy, x, _with_data(pack, data))
    else:
        m = _core_bsr_from_pack(data, pack)
        dx = kref.bsr_matmul_t_gather(dy, m)
        ddata = kref.sddmm_ref(dy, x, m)
        ddata = ddata * jnp.asarray(pack.pad_mask())[:, None, None].astype(ddata.dtype)
    return dx.astype(x.dtype), ddata.astype(data.dtype)


bsr_linear.defvjp(_bsr_linear_fwd, _bsr_linear_bwd)


def _with_data(pack: KernelBSR, data) -> KernelBSR:
    return KernelBSR(data, pack.row_id, pack.col_id, pack.t_perm,
                     pack.real_nnzt, pack.shape, pack.tile)


# --------------------------------------------------------------------------
# convenience wrappers
# --------------------------------------------------------------------------

def bsr_matmul(x: jax.Array, w: KernelBSR, backend: str | None = None):
    """Batched-x entry point: x (..., K) -> (..., N)."""
    backend = backend or default_backend()
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = bsr_linear(x2, w.data, w, backend)
    return y.reshape(*lead, w.shape[0])


def default_plan_backend() -> str:
    """Execution backend for row-grouped plan layouts: the compiled
    plan-consuming Pallas kernel on TPU, the XLA composition elsewhere."""
    return "plan_pallas" if jax.default_backend() == "tpu" else "plan"


def plan_dispatch(x, data_rp, plan, backend: str | None = None):
    """Plan-layout matmul behind a backend switch: 'plan' = the XLA
    gather/einsum/segment-sum composition (exec_plan.plan_matmul),
    'plan_pallas' = the compiled kernel driven by the plan's spill schedule
    (exec_plan.plan_matmul_pallas). Both differentiate; both take the same
    row-grouped (V, P, bn, bk) values."""
    backend = backend or default_plan_backend()
    if backend == "plan_pallas":
        return xp.plan_matmul_pallas(x, data_rp, plan)
    if backend == "plan":
        return xp.plan_matmul(x, data_rp, plan)
    raise ValueError(f"unknown plan backend {backend}")


def plan_q_dispatch(x, qvalues, scales, plan, backend: str | None = None):
    """Quantized-pack matmul behind the same backend switch: 'plan' = the
    dequant-fused XLA composition (exec_plan.plan_q_matmul), 'plan_pallas'
    = the compiled kernel with the scale multiply in the accumulation
    (exec_plan.plan_q_matmul_pallas)."""
    backend = backend or default_plan_backend()
    if backend == "plan_pallas":
        return xp.plan_q_matmul_pallas(x, qvalues, scales, plan)
    if backend == "plan":
        return xp.plan_q_matmul(x, qvalues, scales, plan)
    raise ValueError(f"unknown plan backend {backend}")


def sparsify_weight(w_dense, tile: Tuple[int, int] = (128, 128),
                    nnzt: int | None = None) -> KernelBSR:
    """Host-side packing step (offline, like TVM's relay BSR conversion)."""
    return pack_bsr(np.asarray(jax.device_get(w_dense)), tile, nnzt)
