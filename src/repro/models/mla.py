"""Multi-head Latent Attention (DeepSeek-V2): compressed KV cache via low-rank
joint projection.

Train/prefill path expands K/V from the latent c_kv per token. Decode path
uses the *absorbed* formulation: W_uk is folded into the query so attention
scores are taken directly against the (T, kv_lora_rank) latent cache --
the cache is rank*T instead of 2*H*D*T, which is the technique's point.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import (_masked_row_write, as_slot_positions,
                                    decode_attention, flash_attention,
                                    full_attention, masked_attention,
                                    paged_suffix_positions,
                                    prefill_slot_sources)
from repro.models.common import (apply_rope, init_linear, linear,
                                 paged_row_write, paged_view, rms_norm)


def init_mla(key, cfg):
    d, h = cfg.d_model, cfg.n_heads
    r = cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq": init_linear(ks[0], d, h * (dn + dr), cfg.jdtype),
        "w_dkv": init_linear(ks[1], d, r, cfg.jdtype),       # down: x -> c_kv
        "w_krope": init_linear(ks[2], d, dr, cfg.jdtype),    # shared rope key
        "w_uk": init_linear(ks[3], r, h * dn, cfg.jdtype),   # up: c_kv -> k_nope
        "w_uv": init_linear(ks[4], r, h * dv, cfg.jdtype),   # up: c_kv -> v
        "wo": init_linear(ks[5], h * dv, d, cfg.jdtype),
        "kv_norm": {"scale": jnp.zeros((r,), cfg.jdtype)},
    }


def init_cache_mla(cfg, batch, cache_len, dtype=None, paged=None):
    """Latent decode cache; ``paged`` (models.common.PagedLayout) stores the
    latents in page pools (n_pages, page_size, r) addressed through a
    per-slot page table, sharing ids with the attention pools (one logical
    page serves every layer). ``pos_map`` stays dense (batch, T) so the
    absorbed-decode masking is unchanged."""
    dtype = dtype or cfg.jdtype
    if paged is not None:
        npg = paged.table_width(cache_len)
        return {"c_kv_pages": jnp.zeros(
                    (paged.n_pages, paged.page_size, cfg.kv_lora_rank),
                    dtype),
                "k_rope_pages": jnp.zeros(
                    (paged.n_pages, paged.page_size, cfg.qk_rope_dim),
                    dtype),
                "page_table": jnp.full((batch, npg), -1, jnp.int32),
                "pos_map": jnp.full((batch, cache_len), -1, jnp.int32)}
    return {"c_kv": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, cache_len, cfg.qk_rope_dim), dtype),
            "pos_map": jnp.full((batch, cache_len), -1, jnp.int32)}


def _project_q(p, x, cfg, packs=None):
    b, s, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    q = linear(p["wq"], x, packs and packs.get("wq")).reshape(b, s, h, dn + dr)
    return q[..., :dn], q[..., dn:]


def apply_mla(p, x, cfg, *, positions, cache=None, pos=None, packs=None,
              prefill_len=None, page_slot=None, page_start=None, layer=None):
    """``layer`` set: the latent pools are the layer scan's stacked carry,
    this layer's at ``layer`` (attention.apply_attention)."""
    b, s, d = x.shape
    h, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q_nope, q_rope = _project_q(p, x, cfg, packs)
    q_rope = apply_rope(q_rope, positions, theta=cfg.rope_theta)

    c_kv = rms_norm(linear(p["w_dkv"], x), p["kv_norm"]["scale"])
    k_rope = apply_rope(linear(p["w_krope"], x)[:, :, None, :],
                        positions, theta=cfg.rope_theta)       # (b,s,1,dr)

    if cache is not None and s > 1 and page_slot is not None:
        # chunk/suffix prefill: x holds ONE slot's next prompt slice at
        # absolute positions page_start.. . The latent cache is linear
        # (slot == position, no ring), so the chunk's latents write first
        # and the queries attend EXPANDED K/V materialized from the updated
        # latent view -- the same per-token expansion the one-shot prefill
        # runs, just against cached latents for positions < page_start.
        assert b == 1
        length = s if prefill_len is None else prefill_len
        start = jnp.asarray(page_start, jnp.int32)
        pos_i = start + jnp.arange(s)
        validw = jnp.arange(s) < length
        if "c_kv_pages" in cache:
            npg = cache["page_table"].shape[1]
            pt_row = cache["page_table"][page_slot]              # (NP,)
            pts = jnp.broadcast_to(pt_row, (s, npg))  # a table row per token
            cp = paged_row_write(cache["c_kv_pages"], pts, pos_i, c_kv[0],
                                 validw, layer)
            rp = paged_row_write(cache["k_rope_pages"], pts, pos_i,
                                 k_rope[0, :, 0, :], validw, layer)
            pm_row = paged_suffix_positions(cache["pos_map"].shape[1], start,
                                            length)
            new_cache = {"c_kv_pages": cp, "k_rope_pages": rp,
                         "pos_map": cache["pos_map"].at[page_slot].set(
                             pm_row),
                         "page_table": cache["page_table"]}
            c_view = paged_view(cp, pt_row[None], pm_row[None],
                                layer)                          # (1,T,r)
            r_view = paged_view(rp, pt_row[None], pm_row[None],
                                layer)                          # (1,T,dr)
        else:
            t = cache["c_kv"].shape[1]
            nslots = cache["c_kv"].shape[0]
            dst = jnp.where(validw, pos_i, t)           # OOB: drop padding
            c_row = cache["c_kv"][page_slot].at[dst].set(
                c_kv[0].astype(cache["c_kv"].dtype))
            r_row = cache["k_rope"][page_slot].at[dst].set(
                k_rope[0, :, 0, :].astype(cache["k_rope"].dtype))
            pm = cache["pos_map"]
            if pm.ndim == 1:                            # legacy shared map
                pm = jnp.broadcast_to(pm, (nslots, t))
            pm_row = paged_suffix_positions(t, start, length)
            new_cache = {"c_kv": cache["c_kv"].at[page_slot].set(c_row),
                         "k_rope": cache["k_rope"].at[page_slot].set(r_row),
                         "pos_map": pm.at[page_slot].set(pm_row)}
            c_view, r_view = c_row[None], r_row[None]
        tv = c_view.shape[1]
        k_nope_all = linear(p["w_uk"], c_view).reshape(1, tv, h, dn)
        v_all = linear(p["w_uv"], c_view).reshape(1, tv, h, dv)
        k_all = jnp.concatenate(
            [k_nope_all,
             jnp.broadcast_to(r_view[:, :, None, :], (1, tv, h, dr))],
            axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        vp = jnp.pad(v_all, ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv)))
        qpos = pos_i[None, :, None]                              # (1,S,1)
        ok = (pm_row[None, None, :] >= 0) & (pm_row[None, None, :] <= qpos)
        o = masked_attention(q, k_all, vp, ok)[..., :dv]
        out = linear(p["wo"], o.reshape(1, s, h * dv),
                     packs and packs.get("wo"))
        return out, new_cache

    if cache is None or s > 1:
        # expanded path: materialize per-head K/V from latents
        k_nope = linear(p["w_uk"], c_kv).reshape(b, s, h, dn)
        v = linear(p["w_uv"], c_kv).reshape(b, s, h, dv)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, h, dr))],
                            axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        # pad v to qk head dim so the shared attention kernels apply
        attn = full_attention if s <= 1024 else flash_attention
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv)))
        o = attn(q, k, vp, causal=True)[..., :dv]
        out = linear(p["wo"], o.reshape(b, s, h * dv),
                     packs and packs.get("wo"))
        if cache is None:
            return out, None
        if "c_kv_pages" in cache:
            raise NotImplementedError(
                "whole-cache prompt prefill is undefined for a paged MLA "
                "layout; prefill a dense batch-1 sub-cache and insert it "
                "with write_slot_paged")
        # prompt prefill: bulk-write the latent cache (linear, T >= prompt)
        t = cache["c_kv"].shape[1]
        src, slot_pos = prefill_slot_sources(
            t, s if prefill_len is None else prefill_len, s)
        keep2 = (slot_pos >= 0)[None, :, None]
        new_cache = {
            "c_kv": jnp.where(keep2, jnp.take(c_kv, src, axis=1), 0.0
                              ).astype(cache["c_kv"].dtype),
            "k_rope": jnp.where(keep2, jnp.take(k_rope[:, :, 0, :], src,
                                                axis=1), 0.0
                                ).astype(cache["k_rope"].dtype),
            "pos_map": jnp.broadcast_to(slot_pos[None], (b, t)),
        }
        return out, new_cache

    # ---- absorbed decode: score against the latent cache ----------------
    assert s == 1 and pos is not None
    posv = as_slot_positions(pos, b)                    # ragged per-slot pos
    active = posv >= 0
    rows = jnp.arange(b)
    if "c_kv_pages" in cache:
        # paged latents: scatter the new row into the slot's current page,
        # score against a gathered slot-contiguous view -- elementwise
        # identical to the dense latent cache, so decode stays bit-exact
        pt = cache["page_table"]
        cp = paged_row_write(cache["c_kv_pages"], pt, posv, c_kv[:, 0],
                             active, layer)
        rp = paged_row_write(cache["k_rope_pages"], pt, posv,
                             k_rope[:, 0, 0, :], active, layer)
        pm = _masked_row_write(cache["pos_map"], rows,
                               jnp.maximum(posv, 0), jnp.maximum(posv, 0),
                               active)
        c_cache = paged_view(cp, pt, pm, layer)
        r_cache = paged_view(rp, pt, pm, layer)
        new_cache = {"c_kv_pages": cp, "k_rope_pages": rp, "pos_map": pm,
                     "page_table": pt}
    else:
        t = cache["c_kv"].shape[1]
        slot = jnp.maximum(posv, 0) % t
        c_cache = _masked_row_write(cache["c_kv"], rows, slot, c_kv[:, 0],
                                    active)
        r_cache = _masked_row_write(cache["k_rope"], rows, slot,
                                    k_rope[:, 0, 0, :], active)
        pm = cache["pos_map"]
        if pm.ndim == 1:                                # legacy shared map
            pm = jnp.broadcast_to(pm, (b, t))
        pm = _masked_row_write(pm, rows, slot, jnp.maximum(posv, 0), active)
        new_cache = {"c_kv": c_cache, "k_rope": r_cache, "pos_map": pm}

    w_uk = p["w_uk"]["w"].reshape(h, dn, cfg.kv_lora_rank)    # (h, dn, r)
    q_abs = jnp.einsum("bqhd,hdr->bqhr", q_nope, w_uk)        # (b,1,h,r)
    s_lat = jnp.einsum("bqhr,btr->bhqt", q_abs.astype(jnp.float32),
                       c_cache.astype(jnp.float32))
    s_rope = jnp.einsum("bqhd,btd->bhqt", q_rope.astype(jnp.float32),
                        r_cache.astype(jnp.float32))
    scores = (s_lat + s_rope) * ((dn + dr) ** -0.5)
    ok = (pm >= 0) & (pm <= posv[:, None])              # per-row causal mask
    scores = jnp.where(ok[:, None, None, :], scores, -1e30)
    pr = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqt,btr->bqhr", pr, c_cache.astype(jnp.float32))
    w_uv = p["w_uv"]["w"].reshape(h, dv, cfg.kv_lora_rank)
    o = jnp.einsum("bqhr,hvr->bqhv", ctx, w_uv).astype(x.dtype)
    out = linear(p["wo"], o.reshape(b, 1, h * dv), packs and packs.get("wo"))
    return out, new_cache
