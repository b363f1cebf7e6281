"""Split-K flash-decode attention kernels (Pallas TPU).

One decode step attends a single query token per request slot against that
slot's KV cache. The XLA reference (models/attention.decode_attention)
materializes the (B, Hkv, G, 1, T) score tensor and softmaxes it -- two HBM
round-trips over a tensor that grows with context length. The kernels here
run the flash-style online softmax on chip instead:

  * ``flash_decode``        -- dense (B, T, Hkv, D) slot caches. The grid is
      (B, Hkv, num_split): the KV axis is cut into ``num_split`` chunks
      (split-K) and each grid step folds one chunk into running
      (max, sum, acc) VMEM scratch; TPU grids iterate sequentially, so the
      scratch IS the split-K reduction and the normalized output is written
      by the last split -- no inter-step HBM traffic.
  * ``paged_flash_decode``  -- page-pool caches (serving/paging.py). One KV
      split == one page: the scalar-prefetched page table drives the
      BlockSpec index map, so each grid step DMAs its page from the pool
      *in place*. This kills the dense-view reassembly tax: the PR-7 paged
      decode gathered a (B, T, Hkv, D) contiguous view per step per layer
      before attending; here no view is ever materialized. The pools are
      stored (.., page_size, Hkv*D), the layout the blocks are cut from,
      and the layer scan passes them stacked with the layer index, so
      nothing copies or relayouts a pool on the way in.

Masking follows decode_attention exactly: ``pos_map`` holds the absolute
position stored in each cache slot (-1 = empty), queries see positions
``0 <= kvp <= pos`` (minus the window cut for ring caches). Because masked
lanes are zeroed *before* the exp (never ``exp(-inf - -inf)``), a fully
masked split -- an unmapped page, an empty ring region, an inactive slot --
contributes exact zeros, which is what makes ``paged_flash_decode``
bit-exact vs ``flash_decode`` over the gathered dense view with matching
split boundaries (tests/test_pallas_serving.py).

Kernel selection: ``resolved_decode_kernel()`` reads an explicit
``decode_kernel_override`` context (set by Servable from the ServingSpec at
trace time), else the ``REPRO_DECODE_KERNEL`` env (auto|xla|flash), else
picks flash on TPU for shapes it compiles (``flash_decode_aligned``) and the
XLA path everywhere else (interpret mode stays a correctness oracle, not a
serving path -- docs/PERF.md).
"""
from __future__ import annotations

import contextlib
import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bsr_matmul import pallas_interpret_default

NEG_INF = -1e30

_ENV = "REPRO_DECODE_KERNEL"
DECODE_KERNELS = ("auto", "xla", "flash")
_OVERRIDE: list = []


def flash_decode_aligned(hkv: int, d: int, chunk: int = 8) -> bool:
    """Whether the compiled kernels can take this decode shape: each grid
    step streams one KV head's (chunk, D) slice of the (.., T, Hkv*D) cache
    view, which the TPU (8, 128) tiling accepts when D is a lane multiple
    (or spans the whole row, Hkv == 1) and the chunk a sublane multiple."""
    return (hkv == 1 or d % 128 == 0) and chunk % 8 == 0


def _check_aligned(hkv, d, chunk, interpret):
    if not interpret and not flash_decode_aligned(hkv, d, chunk):
        raise ValueError(
            f"flash decode cannot compile for Hkv={hkv}, head_dim={d}, "
            f"KV chunk {chunk}: head_dim must be a multiple of 128 (or one "
            f"KV head) and the chunk/page a multiple of 8; use "
            f"decode_kernel='xla'")


def resolved_decode_kernel(hkv: int = 1, d: int = 128, chunk: int = 8) -> str:
    """'xla' or 'flash': innermost override context > env > platform.
    'auto' picks flash on TPU for shapes the compiled kernel takes."""
    if _OVERRIDE:
        return _OVERRIDE[-1]
    kind = os.environ.get(_ENV, "").strip() or "auto"
    if kind not in DECODE_KERNELS:
        raise ValueError(f"{_ENV}={kind!r}; expected one of {DECODE_KERNELS}")
    if kind == "auto":
        return ("flash" if jax.default_backend() == "tpu"
                and flash_decode_aligned(hkv, d, chunk) else "xla")
    return kind


@contextlib.contextmanager
def decode_kernel_override(kind):
    """Pin the decode kernel inside this context ('xla'/'flash'). The
    attention decode branch consults it at TRACE time, so wrapping a jit
    closure's body bakes the choice into that executable. None/'auto' is a
    no-op (fall through to env/platform)."""
    if kind in (None, "auto"):
        yield
        return
    assert kind in ("xla", "flash"), kind
    _OVERRIDE.append(kind)
    try:
        yield
    finally:
        _OVERRIDE.pop()


def default_kv_split(t: int) -> int:
    """Split count keeping ~128-position chunks, capped at 8 -- past that
    the per-split (m, l, acc) reduce traffic outweighs the DMA overlap."""
    return max(1, min(8, t // 128))


# --------------------------------------------------------------------------
# shared online-softmax split step
# --------------------------------------------------------------------------

def _flash_decode_kernel(*refs, n_prefetch, num_split, window, scale):
    """One KV split: fold (k, v, kvp) into running (m, l, acc) scratch.

    refs = (*scalar_prefetch, q, k, v, kvp, o, m_scratch, l_scratch, acc).
    prefetch[0] is the per-slot position vector; the paged variant adds the
    flattened page table and the layer index (consumed only by the BlockSpec
    index maps). k/v
    blocks are one KV head's (1, ck, D) slice of the (.., ck, Hkv*D) cache
    view, kvp one split's (1, 1, ck) row of positions.
    """
    pos_ref = refs[0]
    q_ref, k_ref, v_ref, kvp_ref, o_ref, m_ref, l_ref, acc_ref = \
        refs[n_prefetch:]
    b = pl.program_id(0)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                   # (G, D)
    k = k_ref[0]                                      # (ck, D)
    v = v_ref[0]
    s_ = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (G, ck)
    kvp = kvp_ref[0]                                  # (1, ck)
    pos = pos_ref[b]
    ok = (kvp >= 0) & (kvp <= pos)
    if window > 0:
        ok &= kvp > pos - window
    s_ = jnp.where(ok, s_, NEG_INF)
    m_prev = m_ref[:, :1]                             # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
    # masked lanes zero BEFORE exp: a fully masked split keeps m at NEG_INF
    # and must contribute exactly nothing (exp(NEG_INF - NEG_INF) == 1)
    p = jnp.where(ok, jnp.exp(s_ - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)                    # (G, 1)
    l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # (G, D)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(si == num_split - 1)
    def _():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "num_split", "interpret"))
def _flash_decode_call(q4, k, v, kvp, pos, *, window, num_split, interpret):
    b, hkv, g, d = q4.shape
    t = k.shape[1]
    ck = t // num_split
    grid = (b, hkv, num_split)
    # (B, T, Hkv, D) -> (B, T, Hkv*D) and positions -> (B*S, 1, ck) are free
    # reshapes that give every block TPU-tileable trailing dims
    k = k.reshape(b, t, hkv * d)
    v = v.reshape(b, t, hkv * d)
    kvp = kvp.reshape(b * num_split, 1, ck)
    return pl.pallas_call(
        functools.partial(_flash_decode_kernel, n_prefetch=1,
                          num_split=num_split, window=window,
                          scale=d ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda b, h, s, pos: (b, h, 0, 0)),
                pl.BlockSpec((1, ck, d), lambda b, h, s, pos: (b, s, h)),
                pl.BlockSpec((1, ck, d), lambda b, h, s, pos: (b, s, h)),
                pl.BlockSpec((1, 1, ck),
                             lambda b, h, s, pos: (b * num_split + s, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda b, h, s, pos: (b, h, 0, 0)),
            scratch_shapes=[pltpu.VMEM((g, 128), jnp.float32),
                            pltpu.VMEM((g, 128), jnp.float32),
                            pltpu.VMEM((g, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q4.dtype),
        interpret=interpret,
    )(pos, q4, k, v, kvp)


def flash_decode(q, k_cache, v_cache, kv_positions, pos, *, window=0,
                 kv_split=None, interpret=None):
    """Split-K one-step decode: q (B,1,Hq,D) vs dense caches (B,T,Hkv,D).

    Same contract as decode_attention (ragged per-slot ``pos``, shared or
    per-slot ``kv_positions``, ring-cache ``window``). ``kv_split`` chunks
    the KV axis; T is padded with masked slots so every chunk is a multiple
    of 8 positions. Matching split boundaries make two runs of this kernel
    -- e.g. over a paged cache's gathered view vs ``paged_flash_decode`` --
    bit-exact.
    """
    b, _, hq, d = q.shape
    t, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    num_split = min(kv_split or default_kv_split(t), t)
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    kvp = jnp.asarray(kv_positions, jnp.int32)
    kvp = jnp.broadcast_to(kvp[None, :] if kvp.ndim == 1 else kvp, (b, t))
    pad = (-t) % (8 * num_split)
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kvp = jnp.pad(kvp, ((0, 0), (0, pad)), constant_values=-1)
    interpret = pallas_interpret_default(interpret)
    _check_aligned(hkv, d, (t + pad) // num_split, interpret)
    out = _flash_decode_call(q[:, 0].reshape(b, hkv, g, d), k_cache, v_cache,
                             kvp, posv, window=window, num_split=num_split,
                             interpret=interpret)
    return out.reshape(b, 1, hq, d)


# --------------------------------------------------------------------------
# paged variant: one split == one page, gathered in place via the table
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("window", "npg", "interpret"))
def _paged_flash_call(q4, k_pages, v_pages, pt_flat, pm, pos, layer, *,
                      window, npg, interpret):
    b, hkv, g, d = q4.shape
    ps = k_pages.shape[2]
    grid = (b, hkv, npg)
    # the stacked pools (P, pages, ps, Hkv*D) are read where they lie: one
    # block is one layer's page of one KV head; pos_map -> (B*NP, 1, ps)
    # (a free reshape) so one page is one (1, 1, ps) block
    pm = pm.reshape(b * npg, 1, ps)

    def page_map(b, h, s, pos, pt, layer):
        # unmapped (-1) pages clip to page 0; their pos_map slots are -1 so
        # every lane of the split is masked before the exp
        return (layer[0], jnp.maximum(pt[b * npg + s], 0), 0, h)

    return pl.pallas_call(
        functools.partial(_flash_decode_kernel, n_prefetch=3, num_split=npg,
                          window=window, scale=d ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, d),
                             lambda b, h, s, pos, pt, layer: (b, h, 0, 0)),
                pl.BlockSpec((pl.squeezed, 1, ps, d), page_map),
                pl.BlockSpec((pl.squeezed, 1, ps, d), page_map),
                pl.BlockSpec((1, 1, ps),
                             lambda b, h, s, pos, pt, layer:
                             (b * npg + s, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda b, h, s, pos, pt, layer:
                                   (b, h, 0, 0)),
            scratch_shapes=[pltpu.VMEM((g, 128), jnp.float32),
                            pltpu.VMEM((g, 128), jnp.float32),
                            pltpu.VMEM((g, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q4.dtype),
        interpret=interpret,
    )(pos, pt_flat, layer, q4, k_pages, v_pages, pm)


def paged_flash_decode(q, k_pages, v_pages, page_table, pos_map, pos, *,
                       window=0, layer=None, interpret=None):
    """One-step decode straight off the page pools.

    q (B,1,Hq,D); pools (n_pages, page_size, Hkv*D), or stacked
    (P, n_pages, page_size, Hkv*D) and read at layer ``layer`` (a traced
    scalar: the layer scan's carried pools are never sliced); ``page_table``
    (B, NP) physical page per logical page (-1 = unmapped); ``pos_map``
    (B, NP*page_size) per-slot occupancy as in the dense layout. Each grid
    step DMAs one page via the prefetched table -- the per-step dense-view
    gather of the XLA paged path never happens.
    """
    if layer is None:                   # one layer's pool: a stack of one
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    b, _, hq, d = q.shape
    ps, hkv = k_pages.shape[2], k_pages.shape[3] // d
    npg = page_table.shape[1]
    g = hq // hkv
    assert pos_map.shape == (b, npg * ps), (pos_map.shape, npg, ps)
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    interpret = pallas_interpret_default(interpret)
    _check_aligned(hkv, d, ps, interpret)
    out = _paged_flash_call(q[:, 0].reshape(b, hkv, g, d), k_pages, v_pages,
                            page_table.reshape(-1).astype(jnp.int32),
                            jnp.asarray(pos_map, jnp.int32), posv,
                            jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
                            window=window, npg=npg, interpret=interpret)
    return out.reshape(b, 1, hq, d)
