"""Attention: GQA with RoPE variants, flash-chunked global, banded local,
and single-token decode against (ring-)KV caches.

Memory discipline matters at the assigned shapes (32k prefill): global
attention never materializes an (S, T) score matrix -- it runs a chunked
online-softmax (flash) loop under lax.scan. Local attention gathers only the
window-adjacent KV chunks, so its FLOPs are O(S * window) -- this is what
makes recurrentgemma/mamba runnable at 500k.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_decode import (flash_decode, paged_flash_decode,
                                        resolved_decode_kernel)
from repro.models.common import (apply_rope, init_linear, linear, normal_init,
                                 paged_row_write, paged_view)

NEG_INF = -1e30


def as_slot_positions(pos, batch):
    """Normalize ``pos`` to the ragged per-slot form: an int32 (B,) vector.

    Serving runs request *slots* through the batch dimension, each at its own
    absolute position (repro/serving/engine.py). A scalar ``pos`` -- the
    single-request calling convention -- broadcasts to every row. Negative
    entries mark inactive slots: their cache writes are suppressed and their
    outputs are garbage (finite, but meaningless).
    """
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (batch,))


def _masked_row_write(buf, rows, slot, val, active):
    """Write ``val[i]`` into ``buf[i, slot[i]]`` where ``active[i]``; inactive
    rows keep their previous value (the write happens but stores the old
    content back, so one scatter serves both cases under jit)."""
    keep = jnp.expand_dims(active, tuple(range(1, val.ndim)))
    old = buf[rows, slot]
    return buf.at[rows, slot].set(jnp.where(keep, val, old))


def slot_reset_value(path, x_slice):
    """Reset value for one cache leaf's slot slice (tree_map_with_path
    callback): ``pos_map`` and ``page_table`` slots empty out to -1,
    everything else -- attention KV, quant scales, SSM state, RG-LRU h,
    conv history -- to 0. Shared by every family's ``reset_slot`` (lm.py,
    encdec.py). Page-pool leaves (``*_pages``) never reach this callback:
    their leading axis is physical pages, not slots, so the slot ops skip
    them (lm.reset_slot)."""
    name = getattr(path[-1], "key", None)
    return jnp.full_like(
        x_slice, -1 if name in ("pos_map", "page_table") else 0)


def prefill_slot_sources(t, length, s):
    """Cache-slot gather plan for a one-pass prompt prefill.

    A prompt of ``length`` tokens (padded to ``s``, positions 0..length-1)
    lands in a T-slot ring cache at slot = pos % T; slot j ends up holding
    the LATEST position congruent to j. Returns ``(src, pos)``: per-slot
    source index into the (B, S, ...) prefill tensors (clipped; gather, so
    no duplicate-scatter ordering hazards) and the per-slot absolute
    position (-1 = empty). ``length`` may be a traced scalar -- one compiled
    prefill serves every prompt length in a bucket. Linear caches (T >=
    prompt) are the ring's trivial case: slot j <- position j.
    """
    j = jnp.arange(t)
    last = jnp.asarray(length, jnp.int32) - 1
    src = j + t * ((last - j) // t)         # latest p <= last with p%T == j
    ok = (src >= 0) & (src <= last)
    return jnp.clip(src, 0, s - 1), jnp.where(ok, src, -1)


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim)


def _merge_heads(x):
    b, s, h, d = x.shape
    return x.reshape(b, s, h * d)


# ---------------------------------------------------------------------------
# core attention math (all paths share the grouped-heads convention)
# ---------------------------------------------------------------------------

def full_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                   softcap=0.0, scale=None):
    """Materialized-scores path for short sequences (smoke tests, decode prefill
    of small models). q: (B,S,Hq,D), k/v: (B,T,Hkv,D); ``scale`` defaults to
    D^-0.5."""
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, d)
    scores = jnp.einsum("bshgd,bthd->bhgst", qg, k,
                        preferred_element_type=jnp.float32) * (
                            d ** -0.5 if scale is None else scale)
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    qpos = q_offset + jnp.arange(s)[:, None]
    kpos = jnp.arange(t)[None, :]
    ok = jnp.ones((s, t), bool)
    if causal:
        ok &= kpos <= qpos
    if window > 0:
        ok &= (qpos - kpos) < window
    scores = jnp.where(ok[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", p.astype(v.dtype), v)
    return out.reshape(b, s, hq, d)


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    q_chunk=1024, kv_chunk=1024, softcap=0.0, scale=None):
    """Chunked online-softmax attention with a flash-style custom VJP.

    Forward keeps only O(Cq*Ckv) scores live and saves O(S*d) residuals
    (out + per-position logsumexp); backward recomputes attention blockwise
    (the FA2 schedule). Without the custom VJP, scan autodiff stacks
    per-chunk probability tensors -- O(S^2) residual memory, which the
    dry-run showed dominating the HBM roofline term (docs/PERF.md).
    """
    return _flash(q, k, v, causal, window, q_offset, q_chunk, kv_chunk,
                  softcap, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, window, q_offset, q_chunk, kv_chunk, softcap,
           scale):
    out, _ = _flash_fwd_impl(q, k, v, causal, window, q_offset, q_chunk,
                             kv_chunk, softcap, scale)
    return out


def _blocks(q, k, v, q_chunk, kv_chunk):
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    cq, ck = min(q_chunk, s), min(kv_chunk, t)
    pad_q, pad_k = (-s) % cq, (-t) % ck
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = (s + pad_q) // cq, (t + pad_k) // ck
    qb = q.reshape(b, nq, cq, hkv, g, d).transpose(1, 0, 3, 4, 2, 5)
    kb = k.reshape(b, nk, ck, hkv, d).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(b, nk, ck, hkv, d).transpose(1, 0, 3, 2, 4)
    return qb, kb, vb, (b, s, t, hq, hkv, g, d, cq, ck, nq, nk)


def _tile_ok(qi, ki, cq, ck, t_valid, causal, window, q_offset):
    qpos = q_offset + qi * cq + jnp.arange(cq)[:, None]
    kpos = ki * ck + jnp.arange(ck)[None, :]
    ok = kpos < t_valid
    if causal:
        ok &= kpos <= qpos
    if window > 0:
        ok &= (qpos - kpos) < window
    return ok


def _flash_fwd_impl(q, k, v, causal, window, q_offset, q_chunk, kv_chunk,
                    softcap, scale):
    qb, kb, vb, dims = _blocks(q, k, v, q_chunk, kv_chunk)
    b, s, t, hq, hkv, g, d, cq, ck, nq, nk = dims
    scale = d ** -0.5 if scale is None else scale

    def q_step(_, qi_qblk):
        qi, qblk = qi_qblk
        m0 = jnp.full((b, hkv, g, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, cq, d), jnp.float32)

        def kv_step(carry, ki_kv):
            m, l, acc = carry
            ki, kblk, vblk = ki_kv
            s_ = jnp.einsum("bhgqd,bhkd->bhgqk", qblk, kblk,
                            preferred_element_type=jnp.float32) * scale
            if softcap > 0:
                s_ = jnp.tanh(s_ / softcap) * softcap
            ok = _tile_ok(qi, ki, cq, ck, t, causal, window, q_offset)
            m_new = jnp.maximum(m, jnp.max(
                jnp.where(ok[None, None, None], s_, NEG_INF), axis=-1))
            # store the probability tile in the model dtype: for bf16 models
            # this halves the dominant HBM term (§Perf iter 3); f32 models
            # keep full precision
            p = jnp.where(ok[None, None, None],
                          jnp.exp(s_ - m_new[..., None]), 0.0).astype(q.dtype)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1,
                                   dtype=jnp.float32)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))        # (b,hkv,g,cq)
        return None, (out, lse)

    _, (ob, lseb) = jax.lax.scan(q_step, None, (jnp.arange(nq), qb))
    out = ob.transpose(1, 0, 4, 2, 3, 5).reshape(b, nq * cq, hq, d)
    return out[:, :s].astype(q.dtype), lseb             # lseb (nq,b,hkv,g,cq)


def _flash_vjp_fwd(q, k, v, causal, window, q_offset, q_chunk, kv_chunk,
                   softcap, scale):
    out, lse = _flash_fwd_impl(q, k, v, causal, window, q_offset, q_chunk,
                               kv_chunk, softcap, scale)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, window, q_offset, q_chunk, kv_chunk, softcap,
                   scale, res, dout):
    # softcap>0 bwd falls back to autodiff at the call site (not used by the
    # assigned archs); here softcap is always 0.
    q, k, v, out, lse = res
    qb, kb, vb, dims = _blocks(q, k, v, q_chunk, kv_chunk)
    b, s, t, hq, hkv, g, d, cq, ck, nq, nk = dims
    scale = d ** -0.5 if scale is None else scale
    pad_q = nq * cq - s
    do = dout.astype(q.dtype)
    outp = out.astype(q.dtype)
    if pad_q:
        do = jnp.pad(do, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        outp = jnp.pad(outp, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    dob = do.reshape(b, nq, cq, hkv, g, d).transpose(1, 0, 3, 4, 2, 5)
    outb = outp.reshape(b, nq, cq, hkv, g, d).transpose(1, 0, 3, 4, 2, 5)
    delta = jnp.einsum("nbhgqd,nbhgqd->nbhgq", dob, outb,
                       preferred_element_type=jnp.float32)

    def kv_step(dq_acc, ki_kv):
        ki, kblk, vblk = ki_kv

        def q_step(carry, qi_stuff):
            dk_j, dv_j = carry
            qi, qblk, doq, lseq, dlt = qi_stuff
            s_ = jnp.einsum("bhgqd,bhkd->bhgqk", qblk, kblk,
                            preferred_element_type=jnp.float32) * scale
            ok = _tile_ok(qi, ki, cq, ck, t, causal, window, q_offset)
            # p/ds tiles stored in the model dtype (see fwd note)
            p = jnp.where(ok[None, None, None],
                          jnp.exp(s_ - lseq[..., None]), 0.0).astype(q.dtype)
            dv_j = dv_j + jnp.einsum("bhgqk,bhgqd->bhkd", p, doq,
                                     preferred_element_type=jnp.float32)
            dp = jnp.einsum("bhgqd,bhkd->bhgqk", doq, vblk,
                            preferred_element_type=jnp.float32)
            ds = (p.astype(jnp.float32) * (dp - dlt[..., None]) *
                  scale).astype(q.dtype)
            dk_j = dk_j + jnp.einsum("bhgqk,bhgqd->bhkd", ds, qblk,
                                     preferred_element_type=jnp.float32)
            dq_i = jnp.einsum("bhgqk,bhkd->bhgqd", ds, kblk,
                              preferred_element_type=jnp.float32)
            return (dk_j, dv_j), dq_i

        zk = jnp.zeros((b, hkv, ck, d), jnp.float32)
        (dk_j, dv_j), dq_parts = jax.lax.scan(
            q_step, (zk, zk), (jnp.arange(nq), qb, dob, lse, delta))
        return dq_acc + dq_parts, (dk_j, dv_j)

    dq0 = jnp.zeros((nq, b, hkv, g, cq, d), jnp.float32)
    dq_acc, (dkb, dvb) = jax.lax.scan(kv_step, dq0, (jnp.arange(nk), kb, vb))

    dq = dq_acc.transpose(1, 0, 4, 2, 3, 5).reshape(b, nq * cq, hq, d)[:, :s]
    dk = dkb.transpose(1, 0, 3, 2, 4).reshape(b, nk * ck, hkv, d)[:, :t]
    dv = dvb.transpose(1, 0, 3, 2, 4).reshape(b, nk * ck, hkv, d)[:, :t]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def local_attention(q, k, v, *, window, q_offset=0):
    """Banded causal attention: FLOPs O(S * window), not O(S^2).

    Chunk size C divides the window; each query chunk gathers the previous
    ``window//C`` key chunks plus its own, so out-of-band tiles are never
    computed (true sub-quadratic cost, visible in cost_analysis).
    """
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    assert s == t, "local_attention is a self-attention prefill/train path"
    g = hq // hkv
    c = min(window, 1024)
    assert window % c == 0
    n_prev = window // c
    pad = (-s) % c
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n = (s + pad) // c
    qb = q.reshape(b, n, c, hkv, g, d)
    kc = k.reshape(b, n, c, hkv, d)
    vc = v.reshape(b, n, c, hkv, d)

    def shifted(x, sh):  # chunk i -> chunk i-sh (zero for i<sh)
        return jnp.pad(x, ((0, 0), (sh, 0)) + ((0, 0),) * (x.ndim - 2))[:, :n]

    k_ext = jnp.concatenate([shifted(kc, p) for p in range(n_prev, 0, -1)]
                            + [kc], axis=2)            # (b, n, (n_prev+1)c, hkv, d)
    v_ext = jnp.concatenate([shifted(vc, p) for p in range(n_prev, 0, -1)]
                            + [vc], axis=2)
    scores = jnp.einsum("bnchgd,bnkhd->bnhgck", qb, k_ext,
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    ci = jnp.arange(n)[:, None, None]
    a = jnp.arange(c)[None, :, None]
    bcol = jnp.arange((n_prev + 1) * c)[None, None, :]
    qpos = ci * c + a
    kpos = (ci - n_prev) * c + bcol
    ok = (kpos >= 0) & (kpos <= qpos) & (qpos - kpos < window) & (kpos < s)
    scores = jnp.where(ok[:, None, None][None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnhgck,bnkhd->bnchgd", p.astype(v_ext.dtype), v_ext)
    out = out.reshape(b, n * c, hq, d)
    return out[:, :s]


def decode_attention(q, k_cache, v_cache, kv_positions, pos, *, window=0):
    """One-step decode: q (B,1,Hq,D) vs caches (B,T,Hkv,D).

    ``kv_positions`` holds the absolute position stored in each cache slot
    (-1 = empty) -- this supports both linear caches (slot == position) and
    ring caches for windowed layers (slot == position % window). It is
    either (T,), shared by every batch row, or (B, T) with one map per
    request slot; ``pos`` is correspondingly a scalar or a (B,) vector of
    ragged per-slot positions, so mixed-progress requests share one batched
    decode call with per-row causal/window masks.
    """
    b, _, hq, d = q.shape
    t, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    qg = q.reshape(b, 1, hkv, g, d)
    scores = jnp.einsum("bqhgd,bthd->bhgqt", qg, k_cache,
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    kvp = kv_positions if kv_positions.ndim == 2 else kv_positions[None, :]
    posv = jnp.asarray(pos, jnp.int32)
    posv = posv[:, None] if posv.ndim else posv[None, None]     # (B|1, 1)
    ok = (kvp >= 0) & (kvp <= posv)
    if window > 0:
        ok &= kvp > posv - window
    scores = jnp.where(ok[:, None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqt,bthd->bqhgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(b, 1, hq, d)


def masked_attention(q, k, v, ok, scale=None):
    """Materialized-scores attention under an explicit boolean mask ``ok``
    (B, S, T): q (B,S,Hq,D) vs k/v (B,T,Hkv,D). The paged *suffix prefill*
    path uses this to attend new prompt tokens against a gathered page view
    holding a shared (radix-cache) prefix -- ``ok[b, s, t]`` encodes the
    per-position causal mask ``0 <= kv_pos[t] <= q_pos[s]`` that
    ``decode_attention`` applies for S == 1. ``scale`` defaults to
    D^-0.5."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, d)
    scores = jnp.einsum("bshgd,bthd->bhgst", qg, k,
                        preferred_element_type=jnp.float32) * (
                            d ** -0.5 if scale is None else scale)
    scores = jnp.where(ok[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", p.astype(v.dtype), v)
    return out.reshape(b, s, hq, d)


def paged_suffix_positions(pos_map_len, start, length):
    """pos_map row after a suffix prefill of ``length`` real tokens starting
    at absolute position ``start`` on a slot whose shared-prefix pages
    already cover positions 0..start-1: every position below start + length
    is occupied (slot == position in a linear paged cache)."""
    ar = jnp.arange(pos_map_len)
    return jnp.where(ar < start + length, ar, -1)


# ---------------------------------------------------------------------------
# the GQA attention layer (projections + rope + cache plumbing)
# ---------------------------------------------------------------------------

def init_attention(key, cfg):
    ks = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.head_dim
    p = {"wq": init_linear(ks[0], d, cfg.n_heads * hd, cfg.jdtype),
         "wk": init_linear(ks[1], d, cfg.n_kv_heads * hd, cfg.jdtype),
         "wv": init_linear(ks[2], d, cfg.n_kv_heads * hd, cfg.jdtype),
         "wo": init_linear(ks[3], cfg.n_heads * hd, d, cfg.jdtype)}
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.zeros((hd,), cfg.jdtype)}
        p["k_norm"] = {"scale": jnp.zeros((hd,), cfg.jdtype)}
    return p


def init_cache_attn(cfg, batch, cache_len, window=0, dtype=None, paged=None):
    """Linear cache for global layers, ring cache (len=window) for local.
    ``pos_map`` is (batch, T): each request slot tracks its own occupancy so
    slots at different positions batch into one decode call. With
    cfg.kv_cache_quant, K/V are stored int8 with per-(slot, head) scales
    (dequantized tile-wise inside attention).

    ``paged`` (a ``models.common.PagedLayout``) switches GLOBAL layers to
    the pooled layout: K/V live in (n_pages, page_size, Hkv*D) page pools
    -- heads-major rows, the layout paged_flash_decode cuts its blocks
    from, so no step relayouts a pool --
    addressed through a per-slot ``page_table`` (batch, T // page_size) of
    physical page ids (-1 = unmapped); ``pos_map`` keeps its dense (batch,
    T) form, so the decode masking -- and therefore the attention math --
    is unchanged. Ring caches (window > 0) stay slot-dense: their per-slot
    footprint is already bounded by the window, and ring content depends on
    total sequence length, which breaks prefix-granular page sharing."""
    t = min(cache_len, window) if window > 0 else cache_len
    dtype = dtype or cfg.jdtype
    if paged is not None and window == 0:
        if cfg.kv_cache_quant:
            raise NotImplementedError(
                "kv_layout='paged' does not compose with kv_cache_quant yet"
                " (int8 page pools + per-page scales are future work)")
        npg = paged.table_width(cache_len)
        pshape = (paged.n_pages, paged.page_size,
                  cfg.n_kv_heads * cfg.head_dim)
        return {"k_pages": jnp.zeros(pshape, dtype),
                "v_pages": jnp.zeros(pshape, dtype),
                "page_table": jnp.full((batch, npg), -1, jnp.int32),
                "pos_map": jnp.full((batch, t), -1, jnp.int32)}
    shape = (batch, t, cfg.n_kv_heads, cfg.head_dim)
    if cfg.kv_cache_quant:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:3], jnp.bfloat16),
                "v_scale": jnp.zeros(shape[:3], jnp.bfloat16),
                "pos_map": jnp.full((batch, t), -1, jnp.int32)}
    return {"k": jnp.zeros(shape, dtype),
            "v": jnp.zeros(shape, dtype),
            "pos_map": jnp.full((batch, t), -1, jnp.int32)}


def _quantize_kv(x):
    """(B,S,H,D) -> int8 values + per-(B,S,H) scales."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) *
            scale[..., None].astype(jnp.float32)).astype(dtype)


def _write_prefill_kv(cache, k, v, length):
    """One-pass prompt prefill: replace the slot cache's contents with the
    K/V of positions 0..length-1 (k/v: (B, S>=length, Hkv, D)). Ring caches
    keep the window-latest positions; padding slots read as empty."""
    b, s = k.shape[0], k.shape[1]
    t = cache["k"].shape[1]
    src, slot_pos = prefill_slot_sources(t, length, s)

    def take(vals):
        g = jnp.take(vals, src, axis=1)
        keep = (slot_pos >= 0).reshape((1, t) + (1,) * (g.ndim - 2))
        return jnp.where(keep, g, jnp.zeros_like(g))

    pm = jnp.broadcast_to(slot_pos[None], (b, t))
    if "k_scale" in cache:          # int8 quantized cache
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        return {"k": take(kq), "v": take(vq), "k_scale": take(ks),
                "v_scale": take(vs), "pos_map": pm}
    return {"k": take(k), "v": take(v), "pos_map": pm}


def apply_attention(p, x, cfg, *, positions, window=0, cache=None, pos=None,
                    packs=None, causal=True, kv_override=None,
                    prefill_len=None, page_slot=None, page_start=None,
                    layer=None):
    """x: (B,S,d). Returns (out, new_cache). Train/prefill when cache is None.
    With a cache and S > 1, the call is a *prompt prefill*: normal causal
    attention over the S tokens plus a bulk cache write of positions
    0..prefill_len-1 (prefill_len defaults to S; tokens past it are padding
    and leave no trace -- serving/engine.py buckets prompt lengths).

    A PAGED cache (leaf carries ``k_pages``/``v_pages``/``page_table``;
    init_cache_attn(paged=...)) serves two extra modes:
      * decode (S == 1): the new K/V row scatters into the slot's current
        page (jit OOB-drop masking) and attention runs over a gathered
        slot-contiguous page view -- elementwise identical to the dense
        cache array, so decode stays bit-exact vs the dense oracle;
      * suffix prefill (S > 1 with ``page_slot``/``page_start``): x holds
        ONE slot's new prompt tokens at absolute positions page_start..;
        their K/V scatter into the slot's (already-installed) pages and
        the queries attend over the page view, whose low pages hold a
        shared radix-cache prefix that was never re-prefilled. Whole-cache
        prefill on a paged layout is not defined -- the engine prefills
        into a dense batch-1 sub-cache and page-scatters it instead
        (lm.write_slot_paged).
    ``layer`` set means the pools are the layer scan's stacked (P, ...)
    carry and this layer's is ``layer`` (models/lm.py).

    A DENSE cache with ``page_slot``/``page_start`` and S > 1 is the same
    suffix/chunk-prefill contract on slot-dense storage (chunked prefill,
    docs/API.md §SLO scheduling): x holds one slot's next prompt slice, the
    queries attend over the slot's current ring content concatenated with
    the fresh chunk K/V (attend-before-write -- see the branch comment),
    and the chunk then ring-writes latest-wins into the slot row. Works for
    global (T = cache_len) and windowed (T = window) layers; int8-quantized
    caches are excluded (the engine gates chunking off for them).

    kv_override: (k, v) tensors for cross-attention (enc-dec).

    When the sparse export fused the q/k/v projections (``packs['wqkv']``,
    repro/serving/export.py), one block-sparse matmul produces all three --
    one gather of x and one dispatch per layer instead of three -- and the
    output is split at the (Hq*D, Hkv*D, Hkv*D) boundaries."""
    from repro.models.common import rms_norm
    b, s, _ = x.shape
    hd = cfg.head_dim
    fused = packs.get("wqkv") if packs else None
    if fused is not None:
        assert kv_override is None, "fused QKV export is self-attention only"
        qkv = linear(p["wqkv"], x, fused)
        dq, dkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
        q = _split_heads(qkv[..., :dq], cfg.n_heads, hd)
        k = _split_heads(qkv[..., dq:dq + dkv], cfg.n_kv_heads, hd)
        v = _split_heads(qkv[..., dq + dkv:], cfg.n_kv_heads, hd)
    else:
        q = _split_heads(linear(p["wq"], x, packs and packs.get("wq")),
                         cfg.n_heads, hd)
    if kv_override is None:
        if fused is None:
            k = _split_heads(linear(p["wk"], x, packs and packs.get("wk")),
                             cfg.n_kv_heads, hd)
            v = _split_heads(linear(p["wv"], x, packs and packs.get("wv")),
                             cfg.n_kv_heads, hd)
    else:
        k, v = kv_override
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"]["scale"])
        k = rms_norm(k, p["k_norm"]["scale"]) if kv_override is None else k
    if cfg.rotary_fraction > 0 and kv_override is None:
        q = apply_rope(q, positions, theta=cfg.rope_theta,
                       rotary_fraction=cfg.rotary_fraction)
        k = apply_rope(k, positions, theta=cfg.rope_theta,
                       rotary_fraction=cfg.rotary_fraction)

    new_cache = cache
    paged = cache is not None and "k_pages" in cache
    if paged and s > 1:
        if page_slot is None:
            raise NotImplementedError(
                "whole-cache prompt prefill is undefined for a paged KV "
                "layout; prefill a dense batch-1 sub-cache and insert it "
                "with write_slot_paged, or pass page_slot/page_start for a "
                "shared-prefix suffix prefill")
        assert kv_override is None and b == 1
        npg = cache["page_table"].shape[1]
        length = s if prefill_len is None else prefill_len
        start = jnp.asarray(page_start, jnp.int32)
        pt_row = cache["page_table"][page_slot]                  # (NP,)
        pos_i = start + jnp.arange(s)
        validw = jnp.arange(s) < length                          # else drop
        pts = jnp.broadcast_to(pt_row, (s, npg))     # one table row per token
        kp = paged_row_write(cache["k_pages"], pts, pos_i, k[0], validw,
                             layer)
        vp = paged_row_write(cache["v_pages"], pts, pos_i, v[0], validw,
                             layer)
        pm_row = paged_suffix_positions(cache["pos_map"].shape[1], start,
                                        length)
        pm = cache["pos_map"].at[page_slot].set(pm_row)
        new_cache = {"k_pages": kp, "v_pages": vp, "pos_map": pm,
                     "page_table": cache["page_table"]}
        k_view = paged_view(kp, pt_row[None], pm_row[None], layer
                            ).reshape(k.shape[0], -1, *k.shape[2:])
        v_view = paged_view(vp, pt_row[None], pm_row[None], layer
                            ).reshape(v.shape[0], -1, *v.shape[2:])
        qpos = pos_i[None, :, None]                              # (1,S,1)
        ok = (pm_row[None, None, :] >= 0) & (pm_row[None, None, :] <= qpos)
        out = masked_attention(q, k_view, v_view, ok)
        out = linear(p["wo"], _merge_heads(out), packs and packs.get("wo"))
        return out, new_cache
    if cache is not None and s > 1 and page_slot is not None:
        # DENSE chunk/suffix prefill: x holds ONE slot's next prompt slice
        # at absolute positions page_start.. against the BATCHED engine
        # cache. Attention runs BEFORE the cache write over a concat of the
        # slot's current ring content and the fresh chunk K/V -- a write-
        # then-view order would let the chunk's own tail overwrite ring
        # slots (slot = pos % window) that earlier chunk queries still need.
        assert kv_override is None and b == 1
        if "k_scale" in cache:
            raise NotImplementedError(
                "chunked prefill does not compose with kv_cache_quant: the "
                "one-shot path attends unquantized chunk K/V, so a chunked "
                "run could not be token-exact against it")
        t = cache["k"].shape[1]
        nslots = cache["k"].shape[0]
        length = s if prefill_len is None else prefill_len
        start = jnp.asarray(page_start, jnp.int32)
        pos_i = start + jnp.arange(s)
        validw = jnp.arange(s) < length
        ck_row = cache["k"][page_slot]                           # (T,H,D)
        cv_row = cache["v"][page_slot]
        pm = cache["pos_map"]
        if pm.ndim == 1:                                # legacy shared map
            pm = jnp.broadcast_to(pm, (nslots, t))
        pm_row = pm[page_slot]                                   # (T,)
        k_eff = jnp.concatenate([ck_row[None], k], axis=1)       # (1,T+S,..)
        v_eff = jnp.concatenate([cv_row[None], v], axis=1)
        kvpos = jnp.concatenate([pm_row, jnp.where(validw, pos_i, -1)])
        qpos = pos_i[None, :, None]                              # (1,S,1)
        ok = (kvpos[None, None, :] >= 0) & (kvpos[None, None, :] <= qpos)
        if window > 0:
            ok &= (qpos - kvpos[None, None, :]) < window
        out = masked_attention(q, k_eff, v_eff, ok)
        # latest-wins ring write of the chunk: prefill_slot_sources' gather
        # plan shifted to absolute positions start..start+length-1; ring
        # slots whose latest congruent position predates the chunk keep
        # their old content
        j = jnp.arange(t)
        last = start + jnp.asarray(length, jnp.int32) - 1
        src_abs = j + t * ((last - j) // t)
        okw = (src_abs >= start) & (src_abs <= last)
        src_rel = jnp.clip(src_abs - start, 0, s - 1)

        def ring_merge(row, chunk):
            keep = okw.reshape((t,) + (1,) * (row.ndim - 1))
            return jnp.where(keep, chunk[0][src_rel].astype(row.dtype), row)

        new_cache = {
            "k": cache["k"].at[page_slot].set(ring_merge(ck_row, k)),
            "v": cache["v"].at[page_slot].set(ring_merge(cv_row, v)),
            "pos_map": pm.at[page_slot].set(
                jnp.where(okw, src_abs, pm_row))}
        out = linear(p["wo"], _merge_heads(out), packs and packs.get("wo"))
        return out, new_cache
    if cache is None or s > 1:
        if not causal:
            out = full_attention(q, k, v, causal=False) if s <= 2048 else \
                flash_attention(q, k, v, causal=False,
                                q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
        elif window > 0 and s > window:
            out = local_attention(q, k, v, window=window)
        elif s <= 1024:
            out = full_attention(q, k, v, causal=True, window=window)
        else:
            out = flash_attention(q, k, v, causal=True, window=window,
                                  q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                                  softcap=cfg.attn_logit_softcap)
        if cache is not None:       # prompt prefill: bulk-write the KV
            assert kv_override is None, "prefill is self-attention only"
            new_cache = _write_prefill_kv(
                cache, k, v, s if prefill_len is None else prefill_len)
    elif paged:
        assert s == 1 and pos is not None and kv_override is None
        posv = as_slot_positions(pos, b)
        active = posv >= 0
        pt = cache["page_table"]
        kp = paged_row_write(cache["k_pages"], pt, posv, k[:, 0], active,
                             layer)
        vp = paged_row_write(cache["v_pages"], pt, posv, v[:, 0], active,
                             layer)
        pm = _masked_row_write(cache["pos_map"], jnp.arange(b),
                               jnp.maximum(posv, 0), jnp.maximum(posv, 0),
                               active)
        new_cache = {"k_pages": kp, "v_pages": vp, "pos_map": pm,
                     "page_table": pt}
        if resolved_decode_kernel(cfg.n_kv_heads, hd,
                                  pm.shape[1] // pt.shape[1]) == "flash":
            # the split-K kernel reads pages in place via the prefetched
            # table -- no per-step dense-view gather
            out = paged_flash_decode(q, kp, vp, pt, pm, posv, window=window,
                                     layer=layer)
        else:
            kv_shape = (b, pm.shape[1], cfg.n_kv_heads, hd)
            k_view = paged_view(kp, pt, pm, layer).reshape(kv_shape)
            v_view = paged_view(vp, pt, pm, layer).reshape(kv_shape)
            out = decode_attention(q, k_view, v_view, pm, posv,
                                   window=window)
    else:
        assert s == 1 and pos is not None
        if kv_override is None:
            t = cache["k"].shape[1]
            posv = as_slot_positions(pos, b)
            active = posv >= 0
            slot = jnp.maximum(posv, 0) % t                 # (B,) ring slots
            rows = jnp.arange(b)
            pm = cache["pos_map"]
            if pm.ndim == 1:                                # legacy shared map
                pm = jnp.broadcast_to(pm, (b, t))
            pm = _masked_row_write(pm, rows, slot, jnp.maximum(posv, 0),
                                   active)
            if "k_scale" in cache:   # int8 quantized cache
                kq, ks = _quantize_kv(k)
                vq, vs = _quantize_kv(v)
                ck = _masked_row_write(cache["k"], rows, slot, kq[:, 0],
                                       active)
                cv = _masked_row_write(cache["v"], rows, slot, vq[:, 0],
                                       active)
                cks = _masked_row_write(cache["k_scale"], rows, slot,
                                        ks[:, 0], active)
                cvs = _masked_row_write(cache["v_scale"], rows, slot,
                                        vs[:, 0], active)
                new_cache = {"k": ck, "v": cv, "k_scale": cks,
                             "v_scale": cvs, "pos_map": pm}
                kd = _dequantize_kv(ck, cks, q.dtype)
                vd = _dequantize_kv(cv, cvs, q.dtype)
                out = decode_attention(q, kd, vd, pm, posv, window=window)
            else:
                ck = _masked_row_write(cache["k"], rows, slot, k[:, 0],
                                       active)
                cv = _masked_row_write(cache["v"], rows, slot, v[:, 0],
                                       active)
                new_cache = {"k": ck, "v": cv, "pos_map": pm}
                if resolved_decode_kernel(cfg.n_kv_heads, hd) == "flash":
                    out = flash_decode(q, ck, cv, pm, posv, window=window)
                else:
                    out = decode_attention(q, ck, cv, pm, posv,
                                           window=window)
        else:
            # cross-attn decode: every encoder position is visible
            t = k.shape[1]
            out = decode_attention(q, k, v, jnp.arange(t), t - 1, window=0)
    out = linear(p["wo"], _merge_heads(out), packs and packs.get("wo"))
    return out, new_cache
