"""Paged KV cache + radix prefix sharing (serving/paging.py,
serving/prefix_cache.py, the engine's kv_layout='paged' path).

The parity contract: a paged engine is BIT-EXACT against the dense-slot
engine for greedy decode on every decode-capable attention/MLA family --
the page pool is pure storage relayout (paged_view reassembles the exact
dense cache array, zeros where unmapped), so the decode einsums are
unchanged. On top of that storage the host-side allocator must never leak
or double-free a page under any lifecycle path (done / cancel / deadline /
preempt / pool exhaustion / chaos), and prefix sharing must reuse pages
copy-on-write without one request's decode ever touching another's state.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LayerKind, ModelConfig
from repro.models import init_model
from repro.models.common import (PagedLayout, paged_bulk_write,
                                 paged_row_write, paged_view)
from repro.runtime import chaos as chaos_mod
from repro.serving import ServingSpec, prepare_servable
from repro.serving.engine import FailureReason, ServingEngine
from repro.serving.paging import PagePool, PagePoolExhausted, pages_needed
from repro.serving.prefix_cache import PrefixCache

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

ATTN_TARGETS = ("attn/wq", "attn/wk", "attn/wv", "attn/wo")


def _attn_cfg():
    return ModelConfig(
        arch="paged-attn-smoke", family="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
        pattern=(LayerKind("attn", "dense"),), dtype="float32")


def _mla_cfg():
    return ModelConfig(
        arch="paged-mla-smoke", family="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=512,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        pattern=(LayerKind("mla", "dense"),), dtype="float32")


def _windowed_cfg(n_layers=2):
    """Mixed local+global attention: windowed layers stay slot-dense,
    global layers page -- the partially-paged cache tree."""
    return ModelConfig(
        arch="paged-window-smoke", family="dense", n_layers=n_layers,
        d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
        vocab_size=512,
        pattern=(LayerKind("attn", "dense", window=16),
                 LayerKind("attn", "dense")), dtype="float32")


# attn and mla scan two periods of one layer; "windowed2" two periods of
# the mixed pair, so a paged layer reading another period's pool fails
CFGS = {"attn": _attn_cfg, "mla": _mla_cfg, "windowed": _windowed_cfg,
        "windowed2": lambda: _windowed_cfg(n_layers=4)}


def _servables(cfg, page_size=8):
    params = init_model(jax.random.PRNGKey(1), cfg)
    mk = lambda **kw: prepare_servable(params, cfg, ServingSpec(
        tile=(16, 16), sparsity=0.5, prune="oneshot",
        targets=ATTN_TARGETS, **kw))
    return mk(), mk(kv_layout="paged", kv_page_size=page_size)


@pytest.fixture(scope="module", params=sorted(CFGS))
def pair(request):
    dense, paged = _servables(CFGS[request.param]())
    return request.param, dense, paged


def _drain(sv, prompts, max_new=8, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("cache_len", 64)
    eng = ServingEngine(sv, max_queue=16, **kw)
    reqs = [eng.submit(list(p), max_new_tokens=max_new) for p in prompts]
    eng.run()
    return eng, reqs


# --------------------------------------------------------------------------
# host allocator + radix tree units
# --------------------------------------------------------------------------

def test_pages_needed():
    assert pages_needed(0, 8) == 0
    assert pages_needed(1, 8) == 1
    assert pages_needed(8, 8) == 1
    assert pages_needed(9, 8) == 2


def test_pool_alloc_release_refcount():
    pool = PagePool(4, 8)
    a = pool.alloc(2)
    assert a == [0, 1] and pool.free_count == 2    # deterministic low-first
    pool.retain(a)                                  # second reference
    pool.release(a)
    assert pool.used_count == 2                     # still held once
    pool.release(a)
    assert pool.free_count == 4 and pool.peak_used == 2
    with pytest.raises(ValueError):
        pool.release([0])                           # double release
    with pytest.raises(PagePoolExhausted):
        pool.alloc(5)
    assert pool.free_count == 4                     # failed alloc: no effect
    pool.check()


def test_prefix_cache_match_insert_evict():
    pool = PagePool(8, 4)
    pc = PrefixCache(pool, 4)
    toks = list(range(12))                          # 3 complete chunks
    pages = pool.alloc(3)
    assert pc.insert(toks, pages) == 3
    got = pc.match(toks)                            # retains for the caller
    assert got == pages
    assert pc.hit_tokens == 12
    assert pc.match(toks, limit=9) == pages[:2]     # cap -> whole chunks only
    assert pc.match([99, 98]) == []
    for p in (got + pages[:2]):
        pool.release([p])                           # caller refs back
    pool.release(pages)                             # allocator's own refs
    assert pool.used_count == 3                     # tree still holds 3
    assert pc.evict(3) == 3
    assert pool.free_count == 8
    pool.check()


# --------------------------------------------------------------------------
# device primitives: JAX -1-index semantics are load-bearing
# --------------------------------------------------------------------------

def test_paged_row_write_drops_invalid():
    pool = jnp.zeros((3, 4, 2), jnp.float32)
    table = jnp.asarray([[1, -1]], jnp.int32)       # page 1 mapped, rest not
    val = jnp.ones((1, 2), jnp.float32)
    # pos 6 -> chunk 1 -> table[-1] = unmapped: the write must DROP, not
    # wrap to the last page (jnp's negative-index gather would)
    out = paged_row_write(pool, table, jnp.asarray([6]), val,
                          jnp.asarray([True]))
    assert float(jnp.abs(out).sum()) == 0.0
    out = paged_row_write(pool, table, jnp.asarray([2]), val,
                          jnp.asarray([True]))      # chunk 0 -> page 1 row 2
    assert float(out[1, 2].sum()) == 2.0 and float(jnp.abs(out).sum()) == 2.0
    out = paged_row_write(pool, table, jnp.asarray([2]), val,
                          jnp.asarray([False]))     # inactive slot: dropped
    assert float(jnp.abs(out).sum()) == 0.0


def test_paged_view_zeroes_unmapped():
    layout = PagedLayout(page_size=4, n_pages=3)
    pool = jnp.full((3, 4, 2), 7.0, jnp.float32)    # stale NaN-able junk
    table = jnp.asarray([[2, -1]], jnp.int32)
    pos_map = jnp.asarray([[0, 1, 2, -1, -1, -1, -1, -1]], jnp.int32)
    view = paged_view(pool, table, pos_map)
    assert view.shape == (1, 8, 2)
    np.testing.assert_array_equal(np.asarray(view[0, :3]), 7.0)
    np.testing.assert_array_equal(np.asarray(view[0, 3:]), 0.0)


def test_paged_bulk_write_roundtrip():
    vals = jnp.arange(32, dtype=jnp.float32).reshape(16, 2)
    pool = jnp.zeros((4, 4, 2), jnp.float32)
    row = jnp.asarray([3, 1, -1, -1], jnp.int32)    # vals past page 2 drop
    pool = paged_bulk_write(pool, row, vals)
    table = row[None]
    pos_map = jnp.full((1, 16), -1, jnp.int32).at[0, :8].set(jnp.arange(8))
    view = paged_view(pool, table, pos_map)
    np.testing.assert_array_equal(np.asarray(view[0, :8]),
                                  np.asarray(vals[:8]))
    np.testing.assert_array_equal(np.asarray(view[0, 8:]), 0.0)


# --------------------------------------------------------------------------
# engine parity: paged decode is bit-exact vs the dense-slot oracle
# --------------------------------------------------------------------------

PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], list(range(10, 31)), [40, 41]]


def test_paged_engine_bitexact(pair):
    name, dense, paged = pair
    eng_d, res_d = _drain(dense, PROMPTS)
    eng_p, res_p = _drain(paged, PROMPTS)
    if name.startswith("windowed"):
        # only the global layer pages; windowed layers stay slot-dense
        assert eng_p.kv_layout == "paged"
    for rd, rp in zip(res_d, res_p):
        assert rd.status == rp.status == "done"
        assert rd.tokens == rp.tokens, (name, rd.tokens, rp.tokens)
    kv = eng_p.kv_stats()
    assert kv["layout"] == "paged" and kv["peak_pages_used"] > 0
    assert eng_d.kv_stats()["layout"] == "dense"
    eng_p.verify_invariants()


def test_env_var_selects_layout(monkeypatch):
    dense, _ = _servables(_attn_cfg())
    monkeypatch.setenv("REPRO_KV_LAYOUT", "paged")
    eng = ServingEngine(dense, max_slots=2, cache_len=64)
    assert eng.kv_layout == "paged"     # env overrides the dense spec
    monkeypatch.setenv("REPRO_KV_LAYOUT", "bogus")
    with pytest.raises(ValueError):
        ServingEngine(dense, max_slots=2, cache_len=64)


def test_spec_rejects_paged_dp():
    with pytest.raises(ValueError):
        ServingSpec(kv_layout="paged", mesh_shape=(2, 1), partition="dp")


# --------------------------------------------------------------------------
# prefix sharing: CoW reuse, divergence, containment
# --------------------------------------------------------------------------

def test_shared_prefix_bitexact_and_diverges():
    dense, paged = _servables(_attn_cfg())
    shared = list(range(1, 33))                     # 4 full pages
    prompts = [shared + [100, 101, 102], shared + [200, 201]]
    eng_p, res_p = _drain(paged, prompts)
    eng_d, res_d = _drain(dense, prompts)
    for rp, rd in zip(res_p, res_d):
        assert rp.tokens == rd.tokens               # CoW: each one exact
    assert res_p[0].tokens != res_p[1].tokens       # ...and they diverged
    kv = eng_p.kv_stats()
    assert kv["prefix_hit_tokens"] >= 32            # second request shared
    assert kv["prefilled_tokens"] < sum(len(p) for p in prompts)
    eng_p.verify_invariants()


def test_shared_prefix_pages_survive_corrupt_slot():
    """corrupt_slot on one sharer NaN-fills only PRIVATE pages: the other
    sharer (and the prefix cache) must keep decoding finite."""
    _, paged = _servables(_attn_cfg())
    eng = ServingEngine(paged, max_slots=2, cache_len=64, sync_every=1)
    shared = list(range(1, 17))
    a = eng.submit(shared + [100], max_new_tokens=12)
    b = eng.submit(shared + [200], max_new_tokens=12)
    eng.step()                                      # both admitted
    assert a.slot >= 0 and b.slot >= 0
    eng.corrupt_slot(a.slot)
    eng.run()
    assert a.status == "failed"
    assert a.failure.code == FailureReason.NONFINITE_LOGITS
    assert b.status == "done" and len(b.tokens) == 12
    eng.verify_invariants()


# --------------------------------------------------------------------------
# lifecycle hygiene: no leaks under any terminal path
# --------------------------------------------------------------------------

def _pool_balance(eng):
    """Pages not free must all be prefix-cache-owned once idle."""
    return eng._pool.n_pages - eng._pool.free_count - \
        eng._prefix_cache.cached_pages


def test_slot_recycle_no_page_leaks():
    _, paged = _servables(_attn_cfg())
    eng = ServingEngine(paged, max_slots=2, cache_len=64, max_queue=32)
    for wave in range(3):                           # reuse slots 3x over
        reqs = [eng.submit([wave * 7 + t for t in range(1, 6)],
                           max_new_tokens=5) for _ in range(4)]
        eng.run()
        assert all(r.status == "done" for r in reqs)
    assert _pool_balance(eng) == 0
    eng.verify_invariants()


def test_refcounts_under_cancel_deadline_preempt():
    _, paged = _servables(_attn_cfg())
    # pool sized so the preempted victim's retained pages and the
    # preemptor's reservation coexist (default 1-slot pool cannot)
    eng = ServingEngine(paged, max_slots=1, cache_len=64, sync_every=1,
                        max_queue=16, kv_pool_pages=16)
    a = eng.submit(list(range(1, 9)), max_new_tokens=30)
    eng.step()
    b = eng.submit([50, 51, 52], max_new_tokens=30, priority=5)
    eng.step()                                      # preempts a (retained)
    assert a.status == "queued" and a.n_preempted == 1
    assert eng.stats.preemptions == 1
    eng.verify_invariants()                         # saved pages refcounted
    eng.cancel(b)
    c = eng.submit([60, 61], max_new_tokens=2, deadline_s=0.0)
    eng.step()                                      # b cancels, c expires
    assert b.status == "cancelled"
    assert c.status == "failed"
    assert c.failure.code == FailureReason.DEADLINE
    eng.run()                                       # a resumes and finishes
    assert a.status == "done" and len(a.tokens) == 30
    assert eng.stats.page_resumes >= 1
    assert _pool_balance(eng) == 0
    eng.verify_invariants()


def test_preempt_resume_is_cheaper_and_bitexact():
    dense, paged = _servables(_attn_cfg())
    outs = {}
    for tag, sv in (("dense", dense), ("paged", paged)):
        eng = ServingEngine(sv, max_slots=1, cache_len=64, sync_every=2,
                            max_queue=16)
        a = eng.submit(list(range(1, 9)), max_new_tokens=20)
        for _ in range(2):
            eng.step()
        b = eng.submit([20, 21, 22, 23], max_new_tokens=4, priority=10)
        eng.run()
        assert a.status == "done" and b.status == "done"
        outs[tag] = (a.tokens, b.tokens, eng.stats.prefilled_tokens,
                     eng.stats.page_resumes)
    assert outs["dense"][:2] == outs["paged"][:2]   # bit-exact resume
    assert outs["paged"][3] >= 1                    # via page retention...
    assert outs["paged"][2] < outs["dense"][2]      # ...with no re-prefill


# --------------------------------------------------------------------------
# pool exhaustion: backpressure, never a crash
# --------------------------------------------------------------------------

def test_exhaustion_parks_until_pages_free():
    _, paged = _servables(_attn_cfg())
    eng = ServingEngine(paged, max_slots=4, cache_len=64, max_queue=16,
                        kv_pool_pages=3)            # 24 tokens of pool
    x = eng.submit([1, 2, 3, 4], max_new_tokens=4)  # 1 page
    y = eng.submit(list(range(1, 17)), max_new_tokens=8)   # all 3 pages
    eng.run()
    assert x.status == "done" and y.status == "done"
    assert _pool_balance(eng) == 0
    eng.verify_invariants()


def test_exhaustion_fails_oversized_request_when_idle():
    _, paged = _servables(_attn_cfg())
    eng = ServingEngine(paged, max_slots=2, cache_len=64, kv_pool_pages=2)
    r = eng.submit(list(range(1, 25)), max_new_tokens=8)   # needs 4 > 2
    eng.run()
    assert r.status == "failed"
    assert r.failure.code == FailureReason.KV_PAGES
    assert eng._pool.free_count == 2                # nothing leaked
    eng.verify_invariants()


def test_chaos_page_alloc_fault_sheds_per_policy():
    _, paged = _servables(_attn_cfg())
    chaos = chaos_mod.ChaosInjector()
    eng = ServingEngine(paged, max_slots=2, cache_len=64, chaos=chaos)
    chaos.inject(chaos_mod.SITE_PAGE_ALLOC, at=1,
                 exc=PagePoolExhausted(1, 0))
    a = eng.submit([1, 2, 3], max_new_tokens=4)     # hits injected exhaustion
    b = eng.submit([5, 6, 7], max_new_tokens=4)
    eng.run()
    # no active work existed -> the faulted admission fails structurally,
    # the next one proceeds
    assert a.status == "failed"
    assert a.failure.code == FailureReason.KV_PAGES
    assert b.status == "done"
    assert chaos.fired(chaos_mod.SITE_PAGE_ALLOC) == 1
    assert _pool_balance(eng) == 0
    eng.verify_invariants()


# --------------------------------------------------------------------------
# tensor-parallel paged pool
# --------------------------------------------------------------------------

@needs8
def test_tp_paged_pool_bitexact():
    cfg = ModelConfig(
        arch="paged-tp-smoke", family="dense", n_layers=2, d_model=256,
        n_heads=8, n_kv_heads=8, head_dim=32, d_ff=512, vocab_size=512,
        pattern=(LayerKind("attn", "dense"),), dtype="float32")
    params = init_model(jax.random.PRNGKey(3), cfg)
    mk = lambda **kw: prepare_servable(params, cfg, ServingSpec(
        tile=(32, 32), sparsity=0.5, prune="oneshot",
        targets=ATTN_TARGETS, kv_layout="paged", kv_page_size=8, **kw))
    ref = mk()
    tp = mk(mesh_shape=(1, 8), partition="tp")
    eng_r, res_r = _drain(ref, PROMPTS[:2], max_slots=2)
    eng_t, res_t = _drain(tp, PROMPTS[:2], max_slots=2)
    for rr, rt in zip(res_r, res_t):
        assert rr.status == rt.status == "done"
        assert rr.tokens == rt.tokens
    # pool leaves shard kv-heads over "model", never the page axis
    leaves = jax.tree_util.tree_leaves_with_path(eng_t.cache)
    pool_leaves = [(p, x) for p, x in leaves
                   if str(getattr(p[-1], "key", "")).endswith("_pages")]
    assert pool_leaves
    for path, leaf in pool_leaves:
        spec = leaf.sharding.spec
        assert spec[0] is None                      # page axis replicated
        assert "model" in tuple(spec)               # heads sharded
    eng_t.verify_invariants()
