"""Compiled Pallas serving kernels (interpret-mode parity, PR 8).

Two kernel families against their XLA references, both in interpret mode
(the correctness oracle off-TPU, the compiled path on TPU):

  * plan-consuming BSR matmul (kernels/bsr_matmul.plan_dds + the
    exec_plan.plan_linear_pallas custom_vjp): the RowPackPlan's spill
    schedule drives the Pallas grid, so the kernel streams row-grouped
    values with no per-call scatter. Parity vs plan_linear, fwd + bwd,
    including spill-schedule edge rows and fused-QKV packs;
  * split-K flash decode (kernels/flash_decode): online-softmax decode
    attention vs the materialized decode_attention reference across
    window/global configs and split factors, plus the paged variant --
    which must be BIT-exact vs the same flash kernel run over the
    paged_view dense reassembly (same split boundaries, same op order).

Plus: the autotune stub ranks the new candidates sanely, the
'plan_pallas' serving backend round-trips end to end, and the servable
decode-kernel switch ('xla' vs 'flash') preserves greedy tokens.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bsr_matmul import pack_bsr
from repro.kernels.exec_plan import (PlanChoice, build_plan, pack_plan_data,
                                     plan_fused_linear, plan_kernel_sequence,
                                     plan_linear, plan_linear_pallas,
                                     unpack_plan_data)
from repro.kernels.flash_decode import (decode_kernel_override,
                                        default_kv_split, flash_decode,
                                        heads_per_block, paged_flash_decode,
                                        resolved_decode_kernel)
from repro.models.attention import decode_attention
from repro.models.common import paged_view

RNG_SEED = 0


def _sparse_weight(rng, n, k, tile, density):
    w = rng.randn(n, k).astype(np.float32)
    mask = rng.rand(n // tile[0], k // tile[1]) < density
    return w * np.kron(mask, np.ones(tile, np.float32))


def _plan_pack(rng, n, k, tile, density, pad_tiles=0):
    w = _sparse_weight(rng, n, k, tile, density)
    pk = pack_bsr(w, tile)      # pack_bsr may force coverage tiles
    if pad_tiles:
        pk = pack_bsr(w, tile, nnzt=pk.real_nnzt + pad_tiles)
    plan = build_plan(pk)
    return w, plan, pack_plan_data(plan, pk.data)


# --------------------------------------------------------------------------
# plan-consuming Pallas BSR matmul vs plan_linear
# --------------------------------------------------------------------------

@pytest.mark.parametrize("density,pad_tiles", [(0.4, 0), (0.15, 0), (0.4, 5)])
def test_plan_pallas_matches_plan_fwd_bwd(density, pad_tiles):
    """Forward <= 1e-5 and relative grad parity vs plan_linear, including
    padded slots (whose grads must stay exactly zero)."""
    rng = np.random.RandomState(1)
    n, k, m, tile = 96, 128, 24, (16, 32)
    _, plan, data_rp = _plan_pack(rng, n, k, tile, density, pad_tiles)
    x = jnp.asarray(rng.randn(m, k).astype(np.float32))

    y_ref = plan_linear(x, data_rp, plan)
    y_pal = plan_linear_pallas(x, data_rp, plan)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-5)

    loss = lambda fn: jax.grad(
        lambda x_, d_: jnp.sum(fn(x_, d_, plan) ** 2), argnums=(0, 1))
    gx_r, gd_r = loss(plan_linear)(x, data_rp)
    gx_p, gd_p = loss(plan_linear_pallas)(x, data_rp)
    np.testing.assert_allclose(np.asarray(gx_p), np.asarray(gx_r),
                               rtol=2e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gd_p), np.asarray(gd_r),
                               rtol=2e-5, atol=1e-4)
    # padding slots (slot_mask False) never receive gradient
    dead = ~np.asarray(plan.slot_mask)
    assert np.all(np.asarray(gd_p)[dead] == 0.0)


def test_plan_pallas_spill_schedule_edge_rows():
    """A deliberately skewed pattern (one hot row spilling over several
    vrows, some near-empty rows) exercises the write-on-row-change
    protocol across spill boundaries."""
    rng = np.random.RandomState(2)
    n, k, tile = 128, 1024, (16, 64)
    w = np.zeros((n, k), np.float32)
    # hot block row 0 owns every column tile; rows 1..7 one tile each on
    # the diagonal -- the skew the adaptive capacity heuristic spills
    w[:16, :] = rng.randn(16, k)
    for i in range(1, 8):
        w[16 * i: 16 * (i + 1), 64 * i: 64 * (i + 1)] = \
            0.1 * rng.randn(16, 64)
    pk = pack_bsr(w, tile)
    plan = build_plan(pk)
    assert plan.col_idx.shape[0] > n // tile[0], "pattern did not spill"
    seqs = plan_kernel_sequence(plan)
    rows = np.asarray(seqs[0][:-1])
    assert np.all(np.diff(rows) >= 0), "kernel visitation must be row-sorted"
    data_rp = pack_plan_data(plan, pk.data)
    x = jnp.asarray(rng.randn(20, k).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(plan_linear_pallas(x, data_rp, plan)),
        np.asarray(plan_linear(x, data_rp, plan)), rtol=1e-5, atol=1e-4)


def test_plan_pallas_fused_qkv_pack():
    """Fused-QKV-shaped pack (three N segments concatenated) through the
    batched plan_matmul_pallas entry, leading dims preserved."""
    from repro.kernels.exec_plan import plan_matmul_pallas, plan_matmul
    rng = np.random.RandomState(3)
    k, tile = 64, (16, 16)
    segs = [_sparse_weight(rng, 48, k, tile, 0.5) for _ in range(3)]
    w = np.concatenate(segs, axis=0)              # (144, 64) fused
    pk = pack_bsr(w, tile)
    plan = build_plan(pk)
    data_rp = pack_plan_data(plan, pk.data)
    x = jnp.asarray(rng.randn(2, 5, k).astype(np.float32))
    y_ref = plan_matmul(x, data_rp, plan)
    y_pal = plan_matmul_pallas(x, data_rp, plan)
    assert y_pal.shape == (2, 5, 144)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-5)


def test_plan_pallas_bias_act_epilogue():
    """The fused bias/activation epilogue matches applying them after the
    XLA plan path."""
    rng = np.random.RandomState(4)
    n, k, m, tile = 64, 96, 16, (16, 16)
    _, plan, data_rp = _plan_pack(rng, n, k, tile, 0.5)
    x = jnp.asarray(rng.randn(m, k).astype(np.float32))
    b = jnp.asarray(rng.randn(n).astype(np.float32))
    for act, fn in [("relu", jax.nn.relu), ("gelu", jax.nn.gelu),
                    ("silu", jax.nn.silu), (None, lambda v: v)]:
        y_ref = fn(plan_linear(x, data_rp, plan) + b)
        y_pal = plan_fused_linear(x, data_rp, plan, bias=b, act=act)
        np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                                   atol=1e-5, err_msg=str(act))


def test_plan_data_roundtrip_through_pallas_grad():
    """unpack_plan_data of the pallas ddata equals the packed-layout grads
    of the XLA path -- the two layouts stay interchangeable."""
    rng = np.random.RandomState(5)
    _, plan, data_rp = _plan_pack(rng, 64, 64, (16, 16), 0.5)
    x = jnp.asarray(rng.randn(8, 64).astype(np.float32))
    g = jax.grad(lambda d: jnp.sum(plan_linear_pallas(x, d, plan) ** 2))(
        data_rp)
    g_ref = jax.grad(lambda d: jnp.sum(plan_linear(x, d, plan) ** 2))(
        data_rp)
    np.testing.assert_allclose(np.asarray(unpack_plan_data(plan, g)),
                               np.asarray(unpack_plan_data(plan, g_ref)),
                               rtol=2e-5, atol=1e-4)


def test_pallas_entries_pick_interpret_from_platform():
    """Every Pallas entry defaults to interpret=None: the platform decides
    (compiled on TPU, interpret elsewhere), so no caller can end up in the
    interpreter on the chip by leaving the flag out."""
    import importlib
    import inspect
    bm = importlib.import_module("repro.kernels.bsr_matmul")
    for fn in (bm.dds, bm.dds_t, bm.sddmm, bm.masked_matmul, bm.plan_dds,
               bm.plan_dds_q, bm.plan_dds_t, bm.plan_sddmm, flash_decode,
               paged_flash_decode):
        assert inspect.signature(fn).parameters["interpret"].default is None
    on_tpu = jax.default_backend() == "tpu"
    assert bm.pallas_interpret_default() is not on_tpu
    assert bm.pallas_interpret_default(True) is True
    assert bm.pallas_interpret_default(False) is False
    # on this platform the default call runs (interpret mode off-TPU)
    rng = np.random.RandomState(12)
    w, plan, data_rp = _plan_pack(rng, 32, 48, (16, 16), 0.5)
    x = jnp.asarray(rng.randn(5, 48).astype(np.float32))
    y = bm.plan_dds(x, data_rp, plan_kernel_sequence(plan), n=32,
                    tile=(16, 16))
    np.testing.assert_allclose(np.asarray(y), x @ w.T, rtol=1e-5, atol=1e-5)


def test_unaligned_tile_refused_when_kernels_compile(monkeypatch, tmp_path):
    """The paper's 32x1 tile cannot run through a compiled Pallas backend:
    it raises a ValueError at export (and at the kernel) instead of dying
    in Mosaic lowering, and the TPU autotuner drops the Pallas arms."""
    import importlib

    from repro.configs.registry import get_config
    from repro.kernels import autotune
    from repro.models import init_model
    from repro.serving import ServingSpec, prepare_servable

    bm = importlib.import_module("repro.kernels.bsr_matmul")
    assert bm.pallas_tile_supported((128, 128))
    assert not bm.pallas_tile_supported((32, 1))
    assert not bm.pallas_tile_supported((32, 128))
    bm.check_pallas_tile((32, 1), interpret=True)     # interpret takes any
    with pytest.raises(ValueError, match="multiples of 128"):
        bm.check_pallas_tile((32, 1), interpret=False)
    rng = np.random.RandomState(13)
    w, plan, data_rp = _plan_pack(rng, 64, 32, (32, 1), 0.5)
    with pytest.raises(ValueError, match="multiples of 128"):
        bm.plan_dds(jnp.ones((8, 32)), data_rp, plan_kernel_sequence(plan),
                    n=64, tile=(32, 1), interpret=False)

    compiled = lambda interpret=None: False if interpret is None \
        else bool(interpret)
    monkeypatch.setattr(bm, "pallas_interpret_default", compiled)
    monkeypatch.setattr(autotune, "pallas_interpret_default", compiled)
    cfg = get_config("bert_base", smoke=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="multiples of 128"):
        prepare_servable(params, cfg, ServingSpec(
            tile=(32, 1), sparsity=0.5, prune="oneshot",
            backend="plan_pallas"))
    with pytest.raises(ValueError, match="multiples of 128"):
        prepare_servable(params, cfg, ServingSpec(
            tile=(32, 1), sparsity=0.5, prune="oneshot",
            backend="plan_pallas", pack_quant="int8"))
    pk = pack_bsr(_sparse_weight(rng, 64, 32, (32, 1), 0.5), (32, 1))
    choice = autotune.choose_backend(
        pk, 8, stub=True, quant="int8",
        cache=autotune.AutotuneCache(str(tmp_path / "at.json")))
    assert not set(choice.costs) & set(autotune.INTERPRET_ONLY)


# --------------------------------------------------------------------------
# split-K flash decode vs decode_attention
# --------------------------------------------------------------------------

def _decode_case(rng, b, t, hq, hkv, d, ragged=True):
    q = jnp.asarray(rng.randn(b, 1, hq, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, hkv, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, hkv, d).astype(np.float32))
    kvp = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    if ragged:
        pos = jnp.asarray(rng.randint(0, t, size=b), jnp.int32)
        pos = pos.at[0].set(t - 1)
        if b > 1:
            pos = pos.at[1].set(-1)        # inactive slot
    else:
        pos = jnp.full((b,), t - 1, jnp.int32)
    return q, k, v, kvp, pos


@pytest.mark.parametrize("window", [0, 8])
@pytest.mark.parametrize("kv_split", [1, 2, 4])
def test_flash_decode_matches_xla(window, kv_split):
    rng = np.random.RandomState(6)
    b, t, hq, hkv, d = 3, 32, 8, 4, 16
    q, k, v, kvp, pos = _decode_case(rng, b, t, hq, hkv, d)
    out_ref = decode_attention(q, k, v, kvp, pos, window=window)
    out_fl = flash_decode(q, k, v, kvp, pos, window=window,
                          kv_split=kv_split)
    active = np.asarray(pos) >= 0
    np.testing.assert_allclose(np.asarray(out_fl)[active],
                               np.asarray(out_ref)[active], atol=1e-5)


def test_flash_decode_mha_and_scalar_pos():
    """hq == hkv (no grouping) and scalar pos / 1-D kv_positions inputs."""
    rng = np.random.RandomState(7)
    b, t, h, d = 2, 24, 4, 8
    q = jnp.asarray(rng.randn(b, 1, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    kvp = jnp.arange(t, dtype=jnp.int32)             # shared 1-D map
    out_ref = decode_attention(q, k, v, kvp, t - 1)
    out_fl = flash_decode(q, k, v, kvp, t - 1)
    np.testing.assert_allclose(np.asarray(out_fl), np.asarray(out_ref),
                               atol=1e-5)


def _paged_case(rng, n_layers=None):
    """Pools (n_pages, ps, Hkv*D) -- stacked (n_layers, ...) with distinct
    contents per layer when ``n_layers`` is set -- two slots' page tables
    (one with a hole), their pos maps, positions and queries."""
    b, npg, ps, hkv, hq, d = 2, 4, 8, 2, 4, 16
    n_pages = b * npg + 3
    lead = () if n_layers is None else (n_layers,)
    kp = jnp.asarray(rng.randn(*lead, n_pages, ps, hkv * d).astype(
        np.float32))
    vp = jnp.asarray(rng.randn(*lead, n_pages, ps, hkv * d).astype(
        np.float32))
    # slot 0: 3 mapped pages + 1 hole; slot 1: 2 mapped pages
    table = jnp.asarray([[2, 5, 7, -1], [1, 9, -1, -1]], jnp.int32)
    pos_map = np.full((b, npg * ps), -1, np.int32)
    pos_map[0, : 3 * ps] = np.arange(3 * ps)
    pos_map[1, : 2 * ps] = np.arange(2 * ps)
    pos = jnp.asarray([3 * ps - 1, ps + 3], jnp.int32)
    q = jnp.asarray(rng.randn(b, 1, hq, d).astype(np.float32))
    return q, kp, vp, table, jnp.asarray(pos_map), pos, (hkv, d)


def _check_paged_vs_dense_view(n_layers):
    q, kp, vp, table, pos_map, pos, (hkv, d) = _paged_case(
        np.random.RandomState(8), n_layers)
    b, npg = table.shape
    for layer in ([None] if n_layers is None else range(n_layers)):
        out_paged = paged_flash_decode(q, kp, vp, table, pos_map, pos,
                                       layer=layer)
        shape = (b, pos_map.shape[1], hkv, d)
        k_view = paged_view(kp, table, pos_map, layer).reshape(shape)
        v_view = paged_view(vp, table, pos_map, layer).reshape(shape)
        out_view = flash_decode(q, k_view, v_view, pos_map, pos,
                                kv_split=npg)
        assert np.array_equal(np.asarray(out_paged), np.asarray(out_view)), \
            "paged flash decode must be bit-exact vs the dense-view path"
        # and allclose vs the XLA reference over the same view
        out_ref = decode_attention(q, k_view, v_view, pos_map, pos)
        np.testing.assert_allclose(np.asarray(out_paged),
                                   np.asarray(out_ref), atol=1e-5)


def test_paged_flash_decode_bit_exact_vs_dense_view():
    """The paged kernel gathers KV pages in place; over the same page
    geometry it must be BIT-exact vs the flash kernel run on the
    paged_view dense reassembly with matching split boundaries."""
    _check_paged_vs_dense_view(None)


@pytest.mark.parametrize("n_layers", [2, 3])
def test_paged_flash_decode_stacked_bit_exact_vs_dense_view(n_layers):
    """The same over a stacked pool (the layer scan's carry): at every
    layer index, against that layer's dense view."""
    _check_paged_vs_dense_view(n_layers)


def test_paged_flash_decode_layer_index_reads_that_layer():
    """A stacked pool read at layer ``l`` is bit-exact against the same
    call on layer ``l``'s pool alone, and the layers' outputs differ, so a
    wrong or ignored index cannot pass."""
    q, kp, vp, table, pos_map, pos, _ = _paged_case(
        np.random.RandomState(12), n_layers=3)
    outs = []
    for layer in range(3):
        stacked = paged_flash_decode(q, kp, vp, table, pos_map, pos,
                                     layer=jnp.int32(layer))
        alone = paged_flash_decode(q, kp[layer], vp[layer], table, pos_map,
                                   pos)
        assert np.array_equal(np.asarray(stacked), np.asarray(alone)), layer
        outs.append(np.asarray(stacked))
    assert not np.array_equal(outs[0], outs[1])
    assert not np.array_equal(outs[1], outs[2])


def test_paged_flash_decode_ignores_stale_pages():
    """Garbage in unmapped/recycled pages never leaks: only pos_map decides
    visibility."""
    rng = np.random.RandomState(9)
    b, npg, ps, hkv, hq, d = 1, 2, 4, 2, 2, 8
    kp = jnp.asarray(rng.randn(6, ps, hkv * d).astype(np.float32))
    vp = jnp.asarray(rng.randn(6, ps, hkv * d).astype(np.float32))
    table = jnp.asarray([[3, -1]], jnp.int32)
    pos_map = np.full((b, npg * ps), -1, np.int32)
    pos_map[0, :ps] = np.arange(ps)
    pos_map = jnp.asarray(pos_map)
    pos = jnp.asarray([ps - 1], jnp.int32)
    q = jnp.asarray(rng.randn(b, 1, hq, d).astype(np.float32))
    base = paged_flash_decode(q, kp, vp, table, pos_map, pos)
    # poison every page except the mapped one
    kp2 = kp.at[0].set(1e6).at[1].set(1e6).at[2].set(1e6).at[4].set(1e6)
    vp2 = vp.at[0].set(1e6).at[5].set(-1e6)
    out = paged_flash_decode(q, kp2, vp2, table, pos_map, pos)
    assert np.array_equal(np.asarray(base), np.asarray(out))


def _paged_slots_case(rng, hkv, g, d):
    """Four slots over pages of 8: slot 0's pages after its position are
    unmapped, slot 1's table has a hole (its pos_map -1 there) before two
    live pages, slot 2 is inactive (pos -1) though it holds pages, slot 3
    sits on the first position of its last mapped page."""
    b, npg, ps = 4, 4, 8
    n_pages = b * npg + 2
    kp = jnp.asarray(rng.randn(n_pages, ps, hkv * d).astype(np.float32))
    vp = jnp.asarray(rng.randn(n_pages, ps, hkv * d).astype(np.float32))
    table = jnp.asarray([[4, 11, -1, -1], [2, -1, 7, 16],
                         [9, 3, -1, -1], [5, 0, 13, -1]], jnp.int32)
    pos = jnp.asarray([ps + 5, 3 * ps + 2, -1, 2 * ps], jnp.int32)
    pos_map = np.full((b, npg * ps), -1, np.int32)
    for i, (row, p) in enumerate(zip(np.asarray(table), np.asarray(pos))):
        for j in range(p + 1):
            if row[j // ps] >= 0:
                pos_map[i, j] = j
    q = jnp.asarray(rng.randn(b, 1, hkv * g, d).astype(np.float32))
    return q, kp, vp, table, jnp.asarray(pos_map), pos


@pytest.mark.parametrize("hkv,g,d", [(2, 1, 16), (2, 2, 16), (4, 1, 32)])
def test_paged_flash_decode_skips_dead_pages(hkv, g, d):
    """Pages past a slot's position, holes and inactive slots read nothing
    and fold nothing: the output still matches the XLA reference over the
    dense view, and an inactive slot's rows are exact zeros."""
    q, kp, vp, table, pos_map, pos = _paged_slots_case(
        np.random.RandomState(13), hkv, g, d)
    out = np.asarray(paged_flash_decode(q, kp, vp, table, pos_map, pos))
    shape = (q.shape[0], pos_map.shape[1], hkv, d)
    ref = np.asarray(decode_attention(
        q, paged_view(kp, table, pos_map).reshape(shape),
        paged_view(vp, table, pos_map).reshape(shape), pos_map, pos))
    active = np.asarray(pos) >= 0
    np.testing.assert_allclose(out[active], ref[active], atol=1e-5)
    assert np.all(out[~active] == 0.0)


def test_paged_flash_decode_head_blocks(monkeypatch):
    """With a budget that fits only two of four KV heads a page, the grid
    takes two head blocks a slot: still the reference, and bit-exact
    against the dense kernel over the view with the same blocks."""
    import importlib
    fd = importlib.import_module("repro.kernels.flash_decode")
    hkv, g, d = 4, 2, 128
    monkeypatch.setattr(fd, "KV_BLOCK_BUDGET", 4 * 8 * 2 * d * 4)
    assert heads_per_block(hkv, d, 8, 4) == 2
    for call in (fd._paged_flash_call, fd._flash_decode_call):
        call.clear_cache()
    q, kp, vp, table, pos_map, pos = _paged_slots_case(
        np.random.RandomState(14), hkv, g, d)
    out = np.asarray(paged_flash_decode(q, kp, vp, table, pos_map, pos))
    shape = (q.shape[0], pos_map.shape[1], hkv, d)
    k_view = paged_view(kp, table, pos_map).reshape(shape)
    v_view = paged_view(vp, table, pos_map).reshape(shape)
    view = np.asarray(flash_decode(q, k_view, v_view, pos_map, pos,
                                   kv_split=table.shape[1]))
    for call in (fd._paged_flash_call, fd._flash_decode_call):
        call.clear_cache()
    assert np.array_equal(out, view)
    ref = np.asarray(decode_attention(q, k_view, v_view, pos_map, pos))
    active = np.asarray(pos) >= 0
    np.testing.assert_allclose(out[active], ref[active], atol=1e-5)
    assert np.all(out[~active] == 0.0)


def test_heads_per_block_fills_the_budget():
    """A grid step takes every KV head its page's double-buffered K and V
    blocks fit in the VMEM budget, in lane-aligned slices."""
    bf16 = 2
    assert heads_per_block(32, 128, 128, bf16) == 32    # DeepSeek-LLM-7B
    assert heads_per_block(8, 128, 128, bf16) == 8      # internlm2, pixtral
    assert heads_per_block(4, 256, 16, bf16) == 4       # gemma3 global
    hb = heads_per_block(64, 256, 128, bf16)            # 32 MiB whole
    assert hb < 64 and 64 % hb == 0 and hb == 32
    assert 4 * 128 * hb * 256 * bf16 <= 8 << 20
    # a slice of heads must be lane-aligned, else the block is the row
    assert heads_per_block(4, 32, 8, 4) == 4
    assert heads_per_block(2, 16, 1 << 20, 4) == 2      # nothing fits


def test_default_kv_split_and_override():
    assert default_kv_split(64) == 1
    assert default_kv_split(512) == 4
    assert default_kv_split(4096) == 8
    assert resolved_decode_kernel() in ("xla", "flash")
    with decode_kernel_override("flash"):
        assert resolved_decode_kernel() == "flash"
        with decode_kernel_override("xla"):
            assert resolved_decode_kernel() == "xla"   # innermost wins
    prev = os.environ.get("REPRO_DECODE_KERNEL")
    try:
        os.environ["REPRO_DECODE_KERNEL"] = "flash"
        assert resolved_decode_kernel() == "flash"
    finally:
        if prev is None:
            os.environ.pop("REPRO_DECODE_KERNEL", None)
        else:
            os.environ["REPRO_DECODE_KERNEL"] = prev


# --------------------------------------------------------------------------
# autotune integration
# --------------------------------------------------------------------------

def test_autotune_stub_ranks_new_candidates():
    from repro.kernels.autotune import (CANDIDATES, DECODE_CANDIDATES,
                                        INTERPRET_ONLY, decode_stub_costs,
                                        stub_costs)
    rng = np.random.RandomState(10)
    w = _sparse_weight(rng, 64, 64, (16, 16), 0.5)
    pk = pack_bsr(w, (16, 16))
    costs = stub_costs(pk, 128, CANDIDATES)
    assert set(costs) == set(CANDIDATES)
    # plan_pallas skips padded-slot work: strictly cheaper than the
    # flat-stream pallas kernel in the proxy
    assert costs["plan_pallas"] < costs["pallas"]
    if jax.default_backend() != "tpu":
        assert min(costs, key=costs.get) not in INTERPRET_ONLY
    dc = decode_stub_costs(b=4, t=256, hq=8, hkv=4, d=64, kv_split=2)
    assert set(dc) == set(DECODE_CANDIDATES)
    if jax.default_backend() != "tpu":
        assert min(dc, key=dc.get) == "xla"


def test_choose_decode_kernel_stub(tmp_path, monkeypatch):
    from repro.kernels.autotune import AutotuneCache, choose_decode_kernel
    cache = AutotuneCache(str(tmp_path / "at.json"))
    c = choose_decode_kernel(b=4, t=128, hq=4, hkv=2, d=16, stub=True,
                             cache=cache)
    assert c.backend in ("xla", "flash")
    assert not c.cache_hit and c.mode == "stub"
    c2 = choose_decode_kernel(b=4, t=128, hq=4, hkv=2, d=16, stub=True,
                              cache=cache)
    assert c2.cache_hit and c2.backend == c.backend
    if jax.default_backend() != "tpu":
        assert c.backend == "xla"
    # frozen timer exercises the wall-clock branch deterministically
    timer = lambda name, fn, args: {"xla": 2.0, "flash": 1.0}[name]
    c3 = choose_decode_kernel(b=2, t=32, hq=4, hkv=2, d=8, stub=False,
                              cache=cache, timer=timer)
    assert c3.backend == "flash" and c3.mode == "wallclock"
    # attention-free shapes (pure-SSM configs have n_kv_heads=0) must be
    # rejected up front, not ZeroDivide inside the measurement
    with pytest.raises(ValueError, match="attention-free"):
        choose_decode_kernel(b=2, t=32, hq=0, hkv=0, d=0, cache=cache)


# --------------------------------------------------------------------------
# serving integration (slow: full prepare_servable pipelines)
# --------------------------------------------------------------------------

_ATTN = ("attn/wq", "attn/wk", "attn/wv", "attn/wo")


def _smoke_setup():
    from repro.configs.registry import get_config
    from repro.models import api as model_api
    cfg = get_config("gemma3_4b", smoke=True)
    params = model_api.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.mark.slow
def test_plan_pallas_backend_end_to_end(tmp_path):
    """ServingSpec(backend='plan_pallas') forward-parity vs 'plan' and
    save/load round-trip (packs rebuilt as PlanChoice)."""
    from repro.serving.servable import load_servable, prepare_servable
    from repro.serving.spec import ServingSpec
    cfg, params = _smoke_setup()
    rng = np.random.RandomState(11)
    batch = {"tokens": jnp.asarray(
        rng.randint(0, cfg.vocab_size, (2, 12)))}
    mk = lambda backend: ServingSpec(tile=(16, 16), sparsity=0.5,
                                     prune="oneshot", targets=_ATTN,
                                     backend=backend)
    sv_plan = prepare_servable(params, cfg, mk("plan"))
    sv_pp = prepare_servable(params, cfg, mk("plan_pallas"))
    assert all(isinstance(pk, PlanChoice) for pk in sv_pp.packs.values())
    y_plan = np.asarray(sv_plan.forward(batch))
    y_pp = np.asarray(sv_pp.forward(batch))
    np.testing.assert_allclose(y_pp, y_plan, atol=1e-4)

    path = str(tmp_path / "sv")
    sv_pp.save(path)
    sv2 = load_servable(path)
    assert sv2.spec.backend == "plan_pallas"
    assert all(isinstance(pk, PlanChoice) for pk in sv2.packs.values())
    np.testing.assert_array_equal(np.asarray(sv2.forward(batch)), y_pp)


@pytest.mark.slow
def test_servable_decode_kernel_flash_parity():
    """decode_kernel='flash' vs 'xla' servables agree on logits (allclose)
    and on greedy tokens over a short decode."""
    from repro.serving.servable import prepare_servable
    from repro.serving.spec import ServingSpec
    cfg, params = _smoke_setup()
    mk = lambda dk: ServingSpec(tile=(16, 16), sparsity=0.5,
                                prune="oneshot", targets=_ATTN,
                                backend="plan", decode_kernel=dk)
    sv_x = prepare_servable(params, cfg, mk("xla"))
    sv_f = prepare_servable(params, cfg, mk("flash"))
    assert sv_x.decode_kernel_kind() == "xla"
    assert sv_f.decode_kernel_kind() == "flash"
    cx = sv_x.init_cache(2, 32)
    cf = sv_f.init_cache(2, 32)
    tx = tf = jnp.asarray([[3], [7]], jnp.int32)
    for step in range(4):
        p = jnp.full((2,), step, jnp.int32)
        lx, cx = sv_x.decode_step(cx, tx, p)
        lf, cf = sv_f.decode_step(cf, tf, p)
        np.testing.assert_allclose(np.asarray(lf), np.asarray(lx),
                                   atol=1e-4)
        tx = jnp.argmax(lx[:, 0], -1)[:, None].astype(jnp.int32)
        tf = jnp.argmax(lf[:, 0], -1)[:, None].astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(tx), np.asarray(tf))


@pytest.mark.slow
def test_servable_decode_kernel_flash_parity_paged():
    """The same over a paged cache. gemma3's two scan periods of (local,
    local, global) stack the global layers' pools in the layer scan's
    carry, so a kernel or write that picks the wrong layer's pool fails;
    the XLA paged decode stays bit-exact against the dense cache."""
    from repro.models.common import PagedLayout
    from repro.serving.servable import prepare_servable
    from repro.serving.spec import ServingSpec
    cfg, params = _smoke_setup()
    assert cfg.layer_plan()[2] >= 2
    mk = lambda dk: ServingSpec(tile=(16, 16), sparsity=0.5,
                                prune="oneshot", targets=_ATTN,
                                backend="plan", decode_kernel=dk)
    sv_x = prepare_servable(params, cfg, mk("xla"))
    sv_f = prepare_servable(params, cfg, mk("flash"))
    layout = PagedLayout(page_size=8, n_pages=2 * 32 // 8)

    def paged(sv):          # slot b owns pages 4b..4b+3, all mapped
        return jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.broadcast_to(
                jnp.arange(x.shape[-2] * x.shape[-1], dtype=x.dtype
                           ).reshape(x.shape[-2:]), x.shape)
            if getattr(p[-1], "key", None) == "page_table" else x,
            sv.init_cache(2, 32, paged=layout))

    cd, cx, cf = sv_x.init_cache(2, 32), paged(sv_x), paged(sv_f)
    t = jnp.asarray([[3], [7]], jnp.int32)
    for step in range(12):              # crosses the first page boundary
        p = jnp.full((2,), step, jnp.int32)
        ld, cd = sv_x.decode_step(cd, t, p)
        lx, cx = sv_x.decode_step(cx, t, p)
        lf, cf = sv_f.decode_step(cf, t, p)
        np.testing.assert_array_equal(np.asarray(lx), np.asarray(ld))
        np.testing.assert_allclose(np.asarray(lf), np.asarray(lx),
                                   atol=1e-4)
        t = jnp.argmax(lx[:, 0], -1)[:, None].astype(jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(t), np.asarray(jnp.argmax(lf[:, 0], -1)[:, None]))


@pytest.mark.slow
def test_env_override_wins_over_spec(monkeypatch):
    from repro.serving.servable import prepare_servable
    from repro.serving.spec import ServingSpec
    cfg, params = _smoke_setup()
    monkeypatch.setenv("REPRO_DECODE_KERNEL", "xla")
    sv = prepare_servable(params, cfg, ServingSpec(
        tile=(16, 16), sparsity=0.5, prune="oneshot", targets=_ATTN,
        backend="plan", decode_kernel="flash"))
    assert sv.decode_kernel_kind() == "xla"
