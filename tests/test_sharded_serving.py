"""Mesh-first serving (spec.mesh_shape): sharded BSR export, sharded slot
caches, tensor-parallel sparse decode.

The parity contract: a servable prepared with ``mesh_shape=(1, 8)`` must
reproduce the single-device servable's logits (<= 1e-5) and greedy tokens
for every decode-capable family, while its plan packs and slot caches
physically partition across the mesh (per-device bytes shrink ~n_shards
fold where divisibility permits).

Multi-device tests need a forced host-platform mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest tests/test_sharded_serving.py

(the ci.yml `devices: 8` matrix leg runs exactly this; under the default
single-device run these tests skip). The pure-kernel ShardedPlan tests and
spec validation run everywhere.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LayerKind, ModelConfig
from repro.configs.registry import get_config
from repro.core.sparsity import prune_to_sparsity
from repro.kernels import exec_plan as xp
from repro.kernels.bsr_matmul import pack_bsr
from repro.kernels.exec_plan import ShardedPlan
from repro.core.pattern_reuse import PatternRegistry
from repro.models import init_model
from repro.serving import ServingSpec, load_servable, prepare_servable

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

ALL_TARGETS = ("attn/wq", "attn/wk", "attn/wv", "attn/wo",
               "ffn/wi", "ffn/wg", "ffn/wo")
ATTN_TARGETS = ("attn/wq", "attn/wk", "attn/wv", "attn/wo")


def _tp_cfg():
    """Dense LM whose projections divide an 8-wide model axis at tile 32:
    wqkv (768, 256) -> 24 block rows, wo 8 block cols, ffn 32/8."""
    return ModelConfig(
        arch="tp-smoke", family="dense", n_layers=4, d_model=256,
        n_heads=8, n_kv_heads=8, head_dim=32, d_ff=1024, vocab_size=1024,
        pattern=(LayerKind("attn", "dense"),), dtype="float32")


def _tp_spec(**kw):
    return ServingSpec(tile=(32, 32), sparsity=0.7, prune="tied",
                       targets=ALL_TARGETS, **kw)


@pytest.fixture(scope="module")
def tp_pair():
    """(params, single-device servable, 8-way TP servable) over _tp_cfg."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    cfg = _tp_cfg()
    params = init_model(jax.random.PRNGKey(1), cfg)
    sv1 = prepare_servable(params, cfg, _tp_spec())
    sv8 = prepare_servable(params, cfg,
                           _tp_spec(mesh_shape=(1, 8), partition="tp"))
    return params, sv1, sv8


def _run_engine(sv, prompts, *, slots=4, cache_len=64, sync_every=4,
                max_new=8, frames=None):
    eng = sv.engine(max_slots=slots, cache_len=cache_len,
                    sync_every=sync_every)
    if frames is not None:
        hs = [eng.submit(p, max_new_tokens=max_new, frames=f)
              for p, f in zip(prompts, frames)]
    else:
        hs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run()
    assert all(h.done for h in hs)
    return [h.tokens for h in hs], eng


# --------------------------------------------------------------------------
# kernel level: ShardedPlan == dense reference (any device count)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("axis,n_shards", [("out", 4), ("in", 4),
                                           ("out", 8), ("in", 8)])
def test_sharded_plan_matches_dense(axis, n_shards):
    rng = np.random.RandomState(0)
    w = rng.randn(128, 128).astype(np.float32)
    pruned, _ = prune_to_sparsity(jnp.asarray(w), (16, 16), 0.6)
    w = np.asarray(pruned)
    pack = pack_bsr(w, (16, 16))
    plan = xp.build_sharded_plan(pack, n_shards, axis)
    assert plan.n_vrows % n_shards == 0
    assert plan.spilled                    # partials always fold
    assert len(plan.shard_fingerprints) == n_shards
    data = xp.pack_plan_data(plan, pack.data)
    x = rng.randn(5, 128).astype(np.float32)
    y = np.asarray(xp.plan_linear(jnp.asarray(x), data, plan))
    np.testing.assert_allclose(y, x @ w.T, atol=1e-4)


def test_sharded_plan_registry_reuse_per_shard():
    """Identical patterns reuse per-shard layouts; shard_stats exposes the
    per-shard hit/miss accounting."""
    rng = np.random.RandomState(1)
    w = rng.randn(64, 64).astype(np.float32)
    pruned, _ = prune_to_sparsity(jnp.asarray(w), (16, 16), 0.5)
    pack = pack_bsr(np.asarray(pruned), (16, 16))
    reg, st = PatternRegistry(), {}
    xp.build_sharded_plan(pack, 4, "out", registry=reg, shard_stats=st)
    first = {s: dict(v) for s, v in st.items()}
    xp.build_sharded_plan(pack, 4, "out", registry=reg, shard_stats=st)
    # second build: every shard answers from the registry (shards with
    # coincidentally identical sub-patterns may even hit on the first)
    assert set(st) == {0, 1, 2, 3}
    assert all(v["hits"] + v["misses"] == 2 for v in st.values())
    assert all(st[s]["hits"] == first[s]["hits"] + 1 for s in st)


@pytest.mark.parametrize("axis,n_shards", [("out", 4), ("in", 4)])
def test_identical_shard_patterns_share_layouts_correctly(axis, n_shards):
    """Regression: shards whose LOCAL sub-patterns coincide (regular
    patterns -- GQA fused qkv hit this) must share a position-independent
    cached layout; the shared layout is re-offset to each shard's global
    rows/cols at assembly."""
    tile = (16, 16)
    blk = np.random.RandomState(0).rand(2, 2) < 0.7
    mask = np.kron(np.ones((4, 2), bool), blk)   # every shard looks alike
    w = np.random.RandomState(1).randn(128, 64).astype(np.float32)
    w *= np.kron(mask, np.ones(tile, np.float32))
    pack = pack_bsr(w, tile)
    reg = PatternRegistry()
    plan = xp.build_sharded_plan(pack, n_shards, axis, registry=reg)
    assert reg.stats.hits > 0              # layouts actually shared
    data = xp.pack_plan_data(plan, pack.data)
    x = np.random.RandomState(2).randn(3, 64).astype(np.float32)
    y = np.asarray(xp.plan_linear(jnp.asarray(x), data, plan))
    np.testing.assert_allclose(y, x @ w.T, atol=1e-4)


def test_indivisible_pattern_raises_and_predicate():
    rng = np.random.RandomState(2)
    w = rng.randn(48, 48).astype(np.float32)   # 3 block rows at tile 16
    pack = pack_bsr(w, (16, 16))
    assert not xp.shard_divisible(pack, 8, "out")
    with pytest.raises(ValueError):
        xp.build_sharded_plan(pack, 8, "out")


def test_spec_validation():
    with pytest.raises(ValueError):
        ServingSpec(partition="nope")
    with pytest.raises(ValueError):            # tp mesh needs data == 1
        ServingSpec(mesh_shape=(2, 4), partition="tp")
    with pytest.raises(ValueError):            # bsr has no sharded layout
        ServingSpec(mesh_shape=(1, 8), partition="tp", backend="bsr")
    spec = ServingSpec(mesh_shape=(2, 4), partition="tp+dp")
    assert spec.model_shards == 4 and spec.data_shards == 2
    rt = ServingSpec.from_dict(spec.to_dict())
    assert rt == spec and rt.mesh_shape == (2, 4)


# --------------------------------------------------------------------------
# export + placement (8-device mesh)
# --------------------------------------------------------------------------

@needs8
def test_sharded_export_shards_packs_and_bytes(tp_pair):
    """Every projection of the divisible config exports as a ShardedPlan
    and per-device pack bytes come out <= 1/4 (here exactly 1/8) of the
    unsharded total -- the acceptance bar of the mesh refactor."""
    _, sv1, sv8 = tp_pair
    assert sv8.packs and all(isinstance(p, ShardedPlan)
                             for p in sv8.packs.values())
    axes = {k.rsplit("/", 1)[1]: p.shard_axis for k, p in sv8.packs.items()}
    assert axes["wqkv"] == "out" and axes["wo"] == "in"
    st = sv8.stats()["sharding"]
    assert st["n_shards"] == 8 and st["sharded_packs"] == len(sv8.packs)
    assert st["pack_bytes_per_device"] <= st["pack_bytes_total"] / 4
    # physical placement: the vrow axis of every packed leaf is split 8-way
    leaf = sv8.params["blocks"][0]["attn"]["wqkv"]["w"]
    shard_shape = leaf.sharding.shard_shape(leaf.shape)
    assert shard_shape[1] == leaf.shape[1] // 8
    # per-shard registry accounting was collected at export
    assert set(st["per_shard_registry"]) == {str(s) for s in range(8)}
    assert all(v["misses"] >= 1 for v in st["per_shard_registry"].values())


@needs8
def test_forward_prefill_decode_many_parity(tp_pair):
    _, sv1, sv8 = tp_pair
    cfg = sv1.cfg
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg.vocab_size, (2, 8))
    np.testing.assert_allclose(np.asarray(sv1.forward(toks)),
                               np.asarray(sv8.forward(toks)), atol=1e-5)
    t0 = jnp.asarray(toks[:, :1])
    pos = jnp.zeros((2,), jnp.int32)
    t1, v1, _ = sv1.decode_many(sv1.init_cache(2, 32), t0, pos, 6)
    t8, v8, _ = sv8.decode_many(sv8.init_cache(2, 32), t0, pos, 6)
    assert np.array_equal(np.asarray(t1), np.asarray(t8))
    assert np.array_equal(np.asarray(v1), np.asarray(v8))


@needs8
@pytest.mark.parametrize("partition,mesh_shape", [
    ("tp", (1, 8)), ("dp", (8, 1)), ("tp+dp", (2, 4))])
def test_engine_parity_all_partitions(tp_pair, partition, mesh_shape):
    """Sharded engine == single-device engine, token for token, for every
    partition mode -- admission, bucketed prefill, fused windows, slot
    recycling all on the sharded cache."""
    params, sv1, sv8 = tp_pair
    cfg = sv1.cfg
    sv = (sv8 if partition == "tp" else prepare_servable(
        params, cfg, _tp_spec(mesh_shape=mesh_shape, partition=partition)))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (3 + 2 * i,)).tolist()
               for i in range(6)]
    ref, _ = _run_engine(sv1, prompts, slots=8)
    out, eng = _run_engine(sv, prompts, slots=8)
    assert out == ref
    if partition != "tp":       # slots shard over "data"
        leaf = eng.cache["blocks"][0]["mix"]["k"]
        shard = leaf.sharding.shard_shape(leaf.shape)
        assert shard[1] == leaf.shape[1] // mesh_shape[0]


@needs8
def test_sharded_cache_lifecycle_never_gathers(tp_pair):
    """write/free/decode keep every cache leaf's sharding -- lifecycle ops
    are in-place sharded scatters, not host round-trips."""
    _, _, sv8 = tp_pair
    eng = sv8.engine(max_slots=4, cache_len=64, sync_every=4)
    before = jax.tree_util.tree_map(lambda x: x.sharding, eng.cache)
    rng = np.random.RandomState(0)
    hs = [eng.submit(rng.randint(0, sv8.cfg.vocab_size, (5,)).tolist(),
                     max_new_tokens=6) for _ in range(6)]
    eng.run()
    assert all(h.done for h in hs)
    after = jax.tree_util.tree_map(lambda x: x.sharding, eng.cache)
    assert before == after
    # heads genuinely split over the model axis (8 kv heads / 8 devices)
    leaf = eng.cache["blocks"][0]["mix"]["k"]
    assert leaf.sharding.shard_shape(leaf.shape)[3] == 1


@needs8
def test_sharded_slot_recycling_is_hygienic(tp_pair):
    """A recycled slot of a sharded cache serves the same tokens as a
    fresh engine -- free_slot zeroing works shard-local."""
    _, _, sv8 = tp_pair
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, sv8.cfg.vocab_size, (4 + i,)).tolist()
               for i in range(4)]
    # 2 slots, 4 requests: slots 0/1 recycle for requests 2/3
    recycled, _ = _run_engine(sv8, prompts, slots=2)
    fresh = [_run_engine(sv8, [p], slots=1)[0][0] for p in prompts]
    assert recycled == fresh


# --------------------------------------------------------------------------
# family matrix: TP decode == single-device decode for every
# decode-capable family (divisibility falls back to replicated packs; the
# mesh path itself must stay exact either way)
# --------------------------------------------------------------------------

@needs8
@pytest.mark.parametrize("arch", ["deepseek_7b", "chatglm3_6b",
                                  "mamba2_780m", "recurrentgemma_9b"])
def test_family_engine_parity_tp8(arch):
    cfg = get_config(arch, smoke=True)
    params = init_model(jax.random.PRNGKey(1), cfg)
    spec = dict(tile=(16, 16), sparsity=0.5, prune="oneshot",
                targets=ATTN_TARGETS)
    sv1 = prepare_servable(params, cfg, ServingSpec(**spec))
    sv8 = prepare_servable(params, cfg, ServingSpec(
        **spec, mesh_shape=(1, 8), partition="tp"))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (3 + 2 * i,)).tolist()
               for i in range(4)]
    ref, _ = _run_engine(sv1, prompts, slots=2, max_new=6)
    out, _ = _run_engine(sv8, prompts, slots=2, max_new=6)
    assert out == ref


@needs8
def test_family_engine_parity_moe_tp8():
    cfg = get_config("deepseek_v2_lite_16b", smoke=True)
    params = init_model(jax.random.PRNGKey(1), cfg)
    spec = dict(tile=(16, 16), sparsity=0.5, prune="oneshot",
                targets=ATTN_TARGETS)
    sv1 = prepare_servable(params, cfg, ServingSpec(**spec))
    sv8 = prepare_servable(params, cfg, ServingSpec(
        **spec, mesh_shape=(1, 8), partition="tp"))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (4 + i,)).tolist()
               for i in range(3)]
    ref, _ = _run_engine(sv1, prompts, slots=2, max_new=5)
    out, _ = _run_engine(sv8, prompts, slots=2, max_new=5)
    assert out == ref


@needs8
def test_family_engine_parity_mla_tp8():
    cfg = ModelConfig(
        arch="mla-tp-smoke", family="dense",
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, vocab_size=512,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        pattern=(LayerKind("mla", "dense"),), dtype="float32")
    params = init_model(jax.random.PRNGKey(1), cfg)
    spec = dict(tile=(16, 16), sparsity=0.5, prune="oneshot",
                targets=ATTN_TARGETS)
    sv1 = prepare_servable(params, cfg, ServingSpec(**spec))
    sv8 = prepare_servable(params, cfg, ServingSpec(
        **spec, mesh_shape=(1, 8), partition="tp"))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (4 + i,)).tolist()
               for i in range(3)]
    ref, _ = _run_engine(sv1, prompts, slots=2, max_new=5)
    out, _ = _run_engine(sv8, prompts, slots=2, max_new=5)
    assert out == ref


@needs8
def test_bert_forward_parity_tp():
    """Encoder-only family: cross-layer-unioned packs shard over a 4-wide
    model axis (12 block rows divide 4, not 8) and batched forward stays
    within tolerance."""
    cfg = get_config("bert_base", smoke=True)
    params = init_model(jax.random.PRNGKey(1), cfg)
    spec = dict(tile=(16, 16), sparsity=0.5, prune="tied",
                cross_layer_union=True)
    sv1 = prepare_servable(params, cfg, ServingSpec(**spec))
    sv4 = prepare_servable(params, cfg, ServingSpec(
        **spec, mesh_shape=(1, 4), partition="tp"))
    assert any(isinstance(p, ShardedPlan) for p in sv4.packs.values())
    toks = np.random.RandomState(0).randint(0, cfg.vocab_size, (4, 16))
    np.testing.assert_allclose(np.asarray(sv1.forward(toks)),
                               np.asarray(sv4.forward(toks)), atol=1e-5)


@needs8
def test_family_engine_parity_audio_tp8():
    """Audio (enc-dec) has no packs route: the mesh path serves it dense
    with GSPMD-sharded params and a sharded slot cache."""
    cfg = get_config("whisper_base", smoke=True)
    params = init_model(jax.random.PRNGKey(1), cfg)
    spec = dict(tile=(16, 16), sparsity=0.5, prune="none")
    sv1 = prepare_servable(params, cfg, ServingSpec(**spec))
    sv8 = prepare_servable(params, cfg, ServingSpec(
        **spec, mesh_shape=(1, 8), partition="tp"))
    rng = np.random.RandomState(0)
    frames = [rng.randn(cfg.n_audio_ctx, cfg.d_model).astype(np.float32)
              for _ in range(2)]
    prompts = [[1], [1, 2]]
    ref, _ = _run_engine(sv1, prompts, slots=2, max_new=4, frames=frames)
    out, _ = _run_engine(sv8, prompts, slots=2, max_new=4, frames=frames)
    assert out == ref


# --------------------------------------------------------------------------
# persistence
# --------------------------------------------------------------------------

@needs8
def test_save_load_roundtrip_sharded(tp_pair, tmp_path):
    """Shard-partitioned packs survive save/load: kinds, shard metadata,
    per-shard fingerprints, placement, and numerics."""
    _, _, sv8 = tp_pair
    sv8.save(str(tmp_path / "sv"))
    lv = load_servable(str(tmp_path / "sv"))
    assert lv.mesh is not None
    assert set(lv.packs) == set(sv8.packs)
    for key, pk in sv8.packs.items():
        lp = lv.packs[key]
        assert isinstance(lp, ShardedPlan)
        assert lp.n_shards == pk.n_shards
        assert lp.shard_axis == pk.shard_axis
        assert lp.shard_fingerprints == pk.shard_fingerprints
    toks = np.random.RandomState(0).randint(0, sv8.cfg.vocab_size, (2, 8))
    np.testing.assert_allclose(np.asarray(sv8.forward(toks)),
                               np.asarray(lv.forward(toks)), atol=1e-6)
    leaf = lv.params["blocks"][0]["attn"]["wqkv"]["w"]
    assert leaf.sharding.shard_shape(leaf.shape)[1] == leaf.shape[1] // 8
