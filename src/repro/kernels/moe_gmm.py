"""Grouped SwiGLU matmul over the experts a device holds (the MoE FFN).

The rows are token-expert assignments, sorted by expert, each expert's run
padded to a multiple of the row tile ``tm`` (:func:`group_layout`), so that
a tile holds rows of one expert alone. For the tile ``t`` of expert
``e = group[t]``::

    out[t] = (silu(x[t] @ wg[e]) * (x[t] @ wi[e])) @ wo[e]

The Pallas kernel (``_moe_gmm_call``) walks the tiles in order: an expert's
weights stream from HBM once per run of its tiles, so a decode step reads
each held expert once per layer. The weights may come stacked over the
layers, (L, G, ...), with the layer index as a scalar: the layer scan then
passes them whole, and no layer's experts are copied out of the stack.
Tiles past the last one in use map every block onto that last tile's, so
they move no data and do no work. Off the TPU the same layout runs through
``jax.lax.ragged_dot`` (:func:`moe_gmm_xla`), which is also the kernel's
gradient.

No capacity: every assignment gets its row, and a row's result depends on
that row alone, so a token's output does not depend on its batch.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bsr_matmul import pallas_interpret_default

#: VMEM the kernel's double-buffered weight blocks may take
WEIGHT_VMEM = 48 << 20


class GroupLayout(NamedTuple):
    """Where each assignment's row lies in the padded, expert-sorted rows.

    ``row_src`` (R,): the assignment each row holds (A for a padding row);
    ``dest`` (A,): the row of each assignment (R where it is dropped);
    ``sizes`` (G,): padded rows per group; ``group`` (R // tm,): the group
    of each tile, the last used tile's past it; ``used`` (1,): tiles in
    use."""

    row_src: jax.Array
    dest: jax.Array
    sizes: jax.Array
    group: jax.Array
    used: jax.Array
    tm: int


def row_tile(rows_per_group: float) -> int:
    """Rows a tile holds: the power of two at or above the expected rows of
    a group, within [16, 128] (a bf16 tile's sublanes; the MXU's rows)."""
    tm = 16
    while tm < 128 and tm < rows_per_group:
        tm *= 2
    return tm


def group_layout(groups, n_groups: int, tm: int, max_rows: int
                 ) -> GroupLayout:
    """Sort assignments ``groups`` (A,) int32 in [0, n_groups] into runs
    padded to ``tm`` rows; ``n_groups`` marks an assignment that is
    dropped here. ``max_rows`` bounds the assignments that are not: the
    padded rows R are a static bound past it."""
    a = groups.shape[0]
    r = -(-(max_rows + n_groups * (tm - 1)) // tm) * tm
    counts = jnp.zeros((n_groups + 1,), jnp.int32).at[groups].add(1)
    sizes = (counts[:n_groups] + tm - 1) // tm * tm
    ends = jnp.cumsum(sizes)
    order = jnp.argsort(groups, stable=True)
    sorted_g = groups[order]
    first = jnp.cumsum(counts) - counts                 # first sorted index
    start = jnp.concatenate([ends - sizes, jnp.full((1,), r, jnp.int32)])
    rank = jnp.arange(a, dtype=jnp.int32) - first[sorted_g]
    dest_sorted = jnp.where(sorted_g < n_groups, start[sorted_g] + rank, r)
    dest = jnp.zeros((a,), jnp.int32).at[order].set(dest_sorted)
    row_src = jnp.full((r,), a, jnp.int32).at[dest].set(
        jnp.arange(a, dtype=jnp.int32), mode="drop")
    n_tiles = r // tm
    used = ends[-1] // tm
    group = jnp.searchsorted(ends, jnp.arange(n_tiles) * tm, side="right")
    group = jnp.minimum(group, n_groups - 1).astype(jnp.int32)
    group = jnp.where(jnp.arange(n_tiles) < used, group,
                      group[jnp.maximum(used - 1, 0)])
    return GroupLayout(row_src, dest, sizes, group,
                       used.reshape(1).astype(jnp.int32), tm)


def moe_gmm_xla(x, wi, wg, wo, sizes):
    """The grouped SwiGLU in XLA: x (R, d) rows in groups of ``sizes``;
    wi/wg (G, d, f), wo (G, f, d) -> (R, d) float32; rows past the groups
    read zero."""
    f32 = jnp.float32
    a = jax.lax.ragged_dot(x, wi, sizes, preferred_element_type=f32)
    g = jax.lax.ragged_dot(x, wg, sizes, preferred_element_type=f32)
    h = (jax.nn.silu(g) * a).astype(wo.dtype)
    return jax.lax.ragged_dot(h, wo, sizes, preferred_element_type=f32)


def _ffn_tile(d, f, itemsize) -> int:
    """Width of the FFN slice a kernel step holds: all of ``f`` where the
    three double-buffered weight blocks fit ``WEIGHT_VMEM``, else the
    widest 128-multiple dividing ``f`` that does."""
    if f % 128:
        return f
    for n in range(1, f // 128 + 1):
        tf = f // n
        if f % n == 0 and tf % 128 == 0 and \
                2 * 3 * d * tf * itemsize <= WEIGHT_VMEM:
            return tf
    return 128


def _moe_gmm_kernel(group_ref, used_ref, layer_ref, x_ref, wi_ref, wg_ref,
                    wo_ref, o_ref, acc_ref, *, nf):
    t, j = pl.program_id(0), pl.program_id(1)

    @pl.when(t < used_ref[0])
    def _():
        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        a = jnp.dot(x, wi_ref[0, 0], preferred_element_type=jnp.float32)
        g = jnp.dot(x, wg_ref[0, 0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * a).astype(wo_ref.dtype)
        acc_ref[...] += jnp.dot(h, wo_ref[0, 0],
                                preferred_element_type=jnp.float32)

        @pl.when(j == nf - 1)
        def _():
            o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("tm", "tf", "interpret"))
def _moe_gmm_call(group, used, layer, x, wi, wg, wo, *, tm, tf, interpret):
    r, d = x.shape
    f = wi.shape[3]
    nf = f // tf

    def tile(t, used):                  # past the last used: the last used
        return jnp.minimum(t, jnp.maximum(used[0] - 1, 0))

    def part(t, j, used):
        return jnp.where(t < used[0], j, nf - 1)

    itemsize = wi.dtype.itemsize
    vmem = (2 * 3 * d * tf * itemsize + 2 * tm * d * (x.dtype.itemsize + 4)
            + tm * d * 4 + 3 * tm * tf * 4 + (8 << 20))
    return pl.pallas_call(
        functools.partial(_moe_gmm_kernel, nf=nf),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(r // tm, nf),
            in_specs=[
                pl.BlockSpec((tm, d), lambda t, j, g, u, l: (tile(t, u), 0)),
                pl.BlockSpec((1, 1, d, tf), lambda t, j, g, u, l:
                             (l[0], g[t], 0, part(t, j, u))),
                pl.BlockSpec((1, 1, d, tf), lambda t, j, g, u, l:
                             (l[0], g[t], 0, part(t, j, u))),
                pl.BlockSpec((1, 1, tf, d), lambda t, j, g, u, l:
                             (l[0], g[t], part(t, j, u), 0)),
            ],
            out_specs=pl.BlockSpec((tm, d),
                                   lambda t, j, g, u, l: (tile(t, u), 0)),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((r, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(max(vmem, 32 << 20))),
        interpret=interpret,
    )(group, used, layer, x, wi, wg, wo)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _moe_gmm_pallas(x, wi, wg, wo, sizes, group, used, layer, tm, interpret):
    tf = _ffn_tile(x.shape[1], wi.shape[3], wi.dtype.itemsize)
    return _moe_gmm_call(group, used, layer, x, wi, wg, wo, tm=tm, tf=tf,
                         interpret=interpret)


def _pallas_fwd(x, wi, wg, wo, sizes, group, used, layer, tm, interpret):
    return (_moe_gmm_pallas(x, wi, wg, wo, sizes, group, used, layer, tm,
                            interpret), (x, wi, wg, wo, sizes, layer))


def _pallas_bwd(tm, interpret, res, dout):
    x, wi, wg, wo, sizes, layer = res
    _, vjp = jax.vjp(lambda x, *w: moe_gmm_xla(
        x, *(a[layer[0]] for a in w), sizes), x, wi, wg, wo)
    return (*vjp(dout), None, None, None, None)


_moe_gmm_pallas.defvjp(_pallas_fwd, _pallas_bwd)


def moe_gmm(x, wi, wg, wo, layout: GroupLayout, *, layer=None, kernel=None,
            interpret=None):
    """The grouped SwiGLU over ``layout``'s rows x (R, d) -> (R, d)
    float32. ``wi``/``wg`` (G, d, f) and ``wo`` (G, f, d), or each stacked
    over the layers, (L, G, ...), and read at ``layer`` (a traced scalar).
    ``kernel`` (default: on a TPU) runs the Pallas kernel, in interpret
    mode off the TPU; otherwise :func:`moe_gmm_xla`."""
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    if not kernel:
        if layer is not None:
            wi, wg, wo = wi[layer], wg[layer], wo[layer]
        return moe_gmm_xla(x, wi, wg, wo, layout.sizes)
    if layer is None:                   # one layer's experts: a stack of one
        wi, wg, wo, layer = wi[None], wg[None], wo[None], 0
    return _moe_gmm_pallas(x, wi, wg, wo, layout.sizes, layout.group,
                           layout.used,
                           jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
                           layout.tm, pallas_interpret_default(interpret))
