"""Split-K flash-decode attention kernels (Pallas TPU).

One decode step attends a single query token per request slot against that
slot's KV cache. The XLA reference (models/attention.decode_attention)
materializes the (B, Hkv, G, 1, T) score tensor and softmaxes it -- two HBM
round-trips over a tensor that grows with context length. The kernels here
run the flash-style online softmax on chip instead:

  * ``flash_decode``        -- dense (B, T, Hkv, D) slot caches. The grid is
      (B, Hkv / hb, num_split): the KV axis is cut into ``num_split``
      chunks (split-K) and each grid step folds one chunk of ``hb`` KV
      heads into running (max, sum, acc) VMEM scratch; TPU grids iterate
      sequentially, so the scratch IS the split-K reduction and the
      normalized output is written by the last split -- no inter-step HBM
      traffic.
  * ``paged_flash_decode``  -- page-pool caches (serving/paging.py). One KV
      split == one page: the scalar-prefetched page table drives the
      BlockSpec index map, so each grid step DMAs its page from the pool
      *in place* -- no dense (B, T, Hkv, D) view is ever gathered. The
      pools are stored (.., page_size, Hkv*D), heads-major, so one page of
      ``hb`` heads is one contiguous (page_size, hb*D) block; the layer
      scan passes them stacked with the layer index, so nothing copies or
      relayouts a pool on the way in. A page that holds no position the
      slot can see -- past its position, unmapped, or any page of an
      inactive slot -- costs neither a DMA nor the fold: its index map
      repeats the slot's last page (an unchanged block index issues no
      copy) and ``pl.when`` skips the body. The layout is linear
      (``pos_map[b, j]`` is -1 or j), so such a page is fully masked and
      skipping it is exact.

``hb`` (``heads_per_block``) is a function of the shape alone: the largest
divisor of Hkv whose double-buffered K and V blocks fit ``KV_BLOCK_BUDGET``
(8 MiB, half the v5e's scoped VMEM), among lane-aligned slices (hb*D a
multiple of 128) or the whole row. The DeepSeek-LLM-7B's 32 heads of 128 at
pages of 128 take all 32 (1 MB of K and of V a step), 8 x 128 GQA takes 8,
gemma3's 4 x 256 at pages of 16 takes 4. A step walked once per head and
page costs a fixed ~0.5 us on a v5e whatever it reads; one per page of all
heads pays for bytes instead.

Masking follows decode_attention exactly: ``pos_map`` holds the absolute
position stored in each cache slot (-1 = empty), queries see positions
``0 <= kvp <= pos`` (minus the window cut for ring caches). Because masked
lanes are zeroed *before* the exp (never ``exp(-inf - -inf)``), a fully
masked split -- an unmapped page, an empty ring region, an inactive slot --
contributes exact zeros, which is what makes ``paged_flash_decode`` (which
skips such a page) bit-exact vs ``flash_decode`` over the gathered dense
view (which folds it) with matching split boundaries
(tests/test_pallas_serving.py).

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
    step streams ``heads_per_block`` KV heads' (chunk, hb*D) slice of the
    (.., T, Hkv*D) cache view, which the TPU (8, 128) tiling accepts when D
    is a lane multiple (or spans the whole row, Hkv == 1) and the chunk a
    sublane multiple."""
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

#: VMEM for one grid step's K and V blocks, double-buffered: half the v5e's
#: 16 MiB scoped default, leaving the rest to the body's temporaries
KV_BLOCK_BUDGET = 8 << 20


def heads_per_block(hkv: int, d: int, chunk: int, itemsize: int) -> int:
    """KV heads one grid step covers: the largest divisor ``hb`` of ``hkv``
    whose K and V blocks of ``chunk`` positions, double-buffered
    (4 * chunk * hb * d * itemsize bytes), fit ``KV_BLOCK_BUDGET``, among
    those the TPU tiling takes -- a lane-aligned slice (hb * d a multiple
    of 128) or the whole row (hb == hkv). If none fits, the smallest
    legal block."""
    legal = [hb for hb in range(1, hkv + 1) if hkv % hb == 0
             and (hb == hkv or hb * d % 128 == 0)]
    fits = [hb for hb in legal
            if 4 * chunk * hb * d * itemsize <= KV_BLOCK_BUDGET]
    return max(fits) if fits else min(legal)


def _fold_heads(q, k, v, ok, m_ref, l_ref, acc_ref, *, hb, scale):
    """Fold one split of ``hb`` KV heads into the running (m, l, acc)
    scratch: head h is query rows [h*G, (h+1)*G) of q and columns
    [h*D, (h+1)*D) of k and v, static lane-aligned slices, each folded
    with the one-head online softmax in its own order of operations."""
    g, d = q.shape[0] // hb, q.shape[1]
    for h in range(hb):
        r, c = slice(h * g, (h + 1) * g), slice(h * d, (h + 1) * d)
        s_ = jax.lax.dot_general(
            q[r], k[:, c], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G, ck)
        s_ = jnp.where(ok, s_, NEG_INF)
        m_prev = m_ref[r, :1]                             # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
        # masked lanes zero BEFORE exp: a fully masked split keeps m at
        # NEG_INF and must contribute exactly nothing
        # (exp(NEG_INF - NEG_INF) == 1)
        p = jnp.where(ok, jnp.exp(s_ - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)                    # (G, 1)
        l_new = l_ref[r, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[r, :] = acc_ref[r, :] * corr + jax.lax.dot_general(
            p, v[:, c], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (G, D)
        m_ref[r, :] = jnp.broadcast_to(m_new, (g, m_ref.shape[1]))
        l_ref[r, :] = jnp.broadcast_to(l_new, (g, l_ref.shape[1]))


def _flash_decode_kernel(*refs, n_prefetch, num_split, window, scale, hb,
                         live=None):
    """One KV split of ``hb`` KV heads: fold (k, v, kvp) into running
    (m, l, acc) scratch.

    refs = (*scalar_prefetch, q, k, v, kvp, o, m_scratch, l_scratch, acc).
    prefetch[0] is the per-slot position vector; the paged variant adds the
    flattened page table and the layer index. q is the block's
    (hb * G, D) query rows, head-major; k/v blocks are ``hb`` KV heads'
    (ck, hb * D) slice of the (.., ck, Hkv * D) cache rows, kvp one
    split's (1, 1, ck) row of positions. ``live(prefetch, b, si)``, when
    given, gates the fold: a split it rejects holds no visible position
    and is skipped.
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

    def fold():
        kvp = kvp_ref[0]                              # (1, ck)
        pos = pos_ref[b]
        ok = (kvp >= 0) & (kvp <= pos)
        if window > 0:
            ok &= kvp > pos - window
        _fold_heads(q_ref[0, 0], k_ref[0], v_ref[0], ok, m_ref, l_ref,
                    acc_ref, hb=hb, scale=scale)

    if live is None:
        fold()
    else:
        pl.when(live(refs[:n_prefetch], b, si))(fold)

    @pl.when(si == num_split - 1)
    def _():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _scratch(rows, d):
    return [pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32)]


@functools.partial(jax.jit,
                   static_argnames=("window", "num_split", "interpret"))
def _flash_decode_call(q4, k, v, kvp, pos, *, window, num_split, interpret):
    b, hkv, g, d = q4.shape
    t = k.shape[1]
    ck = t // num_split
    hb = heads_per_block(hkv, d, ck, k.dtype.itemsize)
    grid = (b, hkv // hb, num_split)
    # (B, T, Hkv, D) -> (B, T, Hkv*D), queries -> (B, Hkv/hb, hb*G, D) and
    # positions -> (B*S, 1, ck) are free reshapes that give every block
    # TPU-tileable trailing dims
    k = k.reshape(b, t, hkv * d)
    v = v.reshape(b, t, hkv * d)
    q4 = q4.reshape(b, hkv // hb, hb * g, d)
    kvp = kvp.reshape(b * num_split, 1, ck)
    out = pl.pallas_call(
        functools.partial(_flash_decode_kernel, n_prefetch=1,
                          num_split=num_split, window=window,
                          scale=d ** -0.5, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, hb * g, d),
                             lambda b, h, s, pos: (b, h, 0, 0)),
                pl.BlockSpec((1, ck, hb * d), lambda b, h, s, pos: (b, s, h)),
                pl.BlockSpec((1, ck, hb * d), lambda b, h, s, pos: (b, s, h)),
                pl.BlockSpec((1, 1, ck),
                             lambda b, h, s, pos: (b * num_split + s, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, hb * g, d),
                                   lambda b, h, s, pos: (b, h, 0, 0)),
            scratch_shapes=_scratch(hb * g, d),
        ),
        out_shape=jax.ShapeDtypeStruct(q4.shape, q4.dtype),
        interpret=interpret,
    )(pos, q4, k, v, kvp)
    return out.reshape(b, hkv, g, d)


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
# paged variant: one split == one page of hb heads, read in place via the
# table; pages past a slot's position cost no DMA and no fold
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("window", "npg", "interpret"))
def _paged_flash_call(q4, k_pages, v_pages, pt_flat, pm, pos, layer, *,
                      window, npg, interpret):
    b, hkv, g, d = q4.shape
    ps = k_pages.shape[2]
    hb = heads_per_block(hkv, d, ps, k_pages.dtype.itemsize)
    grid = (b, hkv // hb, npg)
    # the stacked pools (P, pages, ps, Hkv*D) are read where they lie: one
    # block is one layer's page of hb KV heads, (ps, hb*D) contiguous
    # rows; pos_map -> (B*NP, 1, ps) (a free reshape) so one page is one
    # (1, 1, ps) block
    q4 = q4.reshape(b, hkv // hb, hb * g, d)
    pm = pm.reshape(b * npg, 1, ps)

    def live(b, s, pos, pt):
        # page s of slot b holds a position the slot can see: mapped, and
        # starting at or before the slot's position (the paged layout is
        # linear, pos_map[b, j] is -1 or j, so every later page is masked)
        return (s * ps <= pos[b]) & (pt[b * npg + s] >= 0)

    def split(b, s, pos, pt):
        # a dead page repeats the slot's last page: the block index does
        # not change, so the pipeline issues no DMA for it
        last = jnp.minimum(jnp.maximum(pos[b], 0) // ps, npg - 1)
        return jnp.where(live(b, s, pos, pt), s, last)

    def page_map(b, h, s, pos, pt, layer):
        page = jnp.maximum(pt[b * npg + split(b, s, pos, pt)], 0)
        return (layer[0], page, 0, h)

    out = pl.pallas_call(
        functools.partial(_flash_decode_kernel, n_prefetch=3, num_split=npg,
                          window=window, scale=d ** -0.5, hb=hb,
                          live=lambda pf, b, s: live(b, s, pf[0], pf[1])),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, hb * g, d),
                             lambda b, h, s, pos, pt, layer: (b, h, 0, 0)),
                pl.BlockSpec((pl.squeezed, 1, ps, hb * d), page_map),
                pl.BlockSpec((pl.squeezed, 1, ps, hb * d), page_map),
                pl.BlockSpec((1, 1, ps),
                             lambda b, h, s, pos, pt, layer:
                             (b * npg + split(b, s, pos, pt), 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, hb * g, d),
                                   lambda b, h, s, pos, pt, layer:
                                   (b, h, 0, 0)),
            scratch_shapes=_scratch(hb * g, d),
        ),
        out_shape=jax.ShapeDtypeStruct(q4.shape, q4.dtype),
        interpret=interpret,
    )(pos, pt_flat, layer, q4, k_pages, v_pages, pm)
    return out.reshape(b, hkv, g, d)


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
