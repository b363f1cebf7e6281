"""Ahead-of-time TPU v5e compiles of the serving kernels at real widths.

Interpret mode (the CPU oracle of tests/test_pallas_serving.py) accepts
block shapes the chip's Mosaic compiler refuses, so each kernel of the
serving path is compiled here for a *described* v5e chip -- no chip
attached: the plan-consuming BSR matmul at BERT-base fused-QKV widths, its
int8 variant with both scale granularities, and flash / paged flash decode
at gemma3-4b attention widths. A passing compile is not a chip run; it only
proves the compiler takes the kernel. The compiled text also pins the
kernels' own names, which the benchmark's rooflines look for in the trace.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and every test
worker imports this file.
"""
import ast
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.bsr_matmul import pack_bsr, plan_dds, plan_dds_q
from repro.kernels.exec_plan import (build_plan, pack_plan_data,
                                     plan_kernel_sequence, quant_granularity)
from repro.kernels.flash_decode import flash_decode, paged_flash_decode
from repro.kernels.moe_gmm import _moe_gmm_call, _ffn_tile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 -- no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a TPU executable written to the persistent cache cannot be read
        # back without a chip; keep these compiles out of it
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _qkv_plan(tile=(128, 128), density=0.3):
    """BERT-base fused QKV: (3 x 768, 768) at 80%-ish block sparsity."""
    rng = np.random.RandomState(0)
    n, k = 3 * 768, 768
    mask = rng.rand(n // tile[0], k // tile[1]) < density
    w = rng.randn(n, k).astype(np.float32) * np.kron(
        mask, np.ones(tile, np.float32))
    pk = pack_bsr(w, tile)
    plan = build_plan(pk)
    return plan, pack_plan_data(plan, pk.data).shape


def _plan_dds(one_chip, dtype=jnp.bfloat16, m=384):
    plan, vshape = _qkv_plan()
    seq = plan_kernel_sequence(plan)
    x = jax.ShapeDtypeStruct((m, plan.shape[1]), dtype, sharding=one_chip)
    d = jax.ShapeDtypeStruct(vshape, dtype, sharding=one_chip)
    return _compile(lambda x, d: plan_dds(x, d, seq, n=plan.shape[0],
                                          tile=plan.tile, interpret=False),
                    x, d)


@pytest.mark.parametrize("m", [384, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_plan_dds_compiles(one_chip, dtype, m):
    _plan_dds(one_chip, dtype, m)


@pytest.mark.parametrize("granularity", ["block", "row"])
def test_plan_dds_q_int8_compiles(one_chip, granularity):
    plan, vshape = _qkv_plan()
    assert quant_granularity(plan.tile) == "block"
    seq = plan_kernel_sequence(plan)
    sshape = vshape[:2] if granularity == "block" else (vshape[0], 1)
    x = jax.ShapeDtypeStruct((384, plan.shape[1]), jnp.float32,
                             sharding=one_chip)
    q = jax.ShapeDtypeStruct(vshape, jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct(sshape, jnp.float32, sharding=one_chip)
    _compile(lambda x, q, s: plan_dds_q(
        x, q, s, seq, n=plan.shape[0], tile=plan.tile,
        granularity=granularity, interpret=False), x, q, s)


@pytest.mark.parametrize("hkv,g,d", [(4, 2, 256), (8, 4, 128)])
def test_flash_decode_compiles(one_chip, hkv, g, d):
    """gemma3-4b decode (B=4 slots, 4 KV heads x 2, head_dim 256, T=1024)
    and a head_dim-128 GQA shape."""
    b, t, dt = 4, 1024, jnp.bfloat16
    s = lambda shape, dtype=dt: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    _compile(lambda q, k, v, pm, pos: flash_decode(
        q, k, v, pm, pos, interpret=False),
        s((b, 1, hkv * g, d)), s((b, t, hkv, d)), s((b, t, hkv, d)),
        s((b, t), jnp.int32), s((b,), jnp.int32))


#: paged decode widths: the benchmark's DeepSeek-LLM-7B (32 MHA KV heads
#: of 128, pages of 128, 16 slots x 12 pages, 8 stacked layers) and
#: gemma3-4b's (4 KV heads x 2 of 256, pages of 16)
PAGED_WIDTHS = {"deepseek_7b": (16, 32, 1, 128, 128, 12, 8),
                "gemma3_4b": (4, 4, 2, 256, 16, 64, 2)}


def _paged_flash_decode(one_chip, widths="deepseek_7b"):
    """The kernel as the layer scan calls it: stacked (P, pages, ps,
    Hkv*D) pools read at a traced layer index."""
    b, hkv, g, d, ps, npg, n_layers = PAGED_WIDTHS[widths]
    dt = jnp.bfloat16
    pool = (n_layers, b * npg, ps, hkv * d)
    s = lambda shape, dtype=dt: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    return _compile(lambda q, kp, vp, pt, pm, pos, layer: paged_flash_decode(
        q, kp, vp, pt, pm, pos, layer=layer, interpret=False),
        s((b, 1, hkv * g, d)), s(pool), s(pool), s((b, npg), jnp.int32),
        s((b, npg * ps), jnp.int32), s((b,), jnp.int32), s((), jnp.int32))


def test_paged_flash_decode_compiles(one_chip):
    for widths in PAGED_WIDTHS:
        _paged_flash_decode(one_chip, widths)


def test_paged_decode_window_moves_no_pool(one_chip, monkeypatch):
    """The fused decode window with the compiled paged flash kernel, for a
    small Llama-style model whose pools are deployment-sized (17 MB a
    layer: a pool small enough to sit in VMEM gets prefetched there
    whole): the optimized TPU HLO holds no copy, slice, update-slice or
    reshape of a layer's pool or of the stacked pool
    (tests/test_kv_pool_in_place.py checks the CPU paths)."""
    import importlib
    from repro.configs.base import LayerKind, ModelConfig
    from repro.kernels.flash_decode import decode_kernel_override
    from repro.models import api as model_api
    from repro.models.common import PagedLayout
    from test_kv_pool_in_place import pool_moves_in_hlo
    fd = importlib.import_module("repro.kernels.flash_decode")
    monkeypatch.setattr(fd, "pallas_interpret_default",
                        lambda interpret=None: False)
    cfg = ModelConfig(
        arch="pool-tpu", family="dense", n_layers=3, d_model=256,
        n_heads=4, n_kv_heads=2, head_dim=128, d_ff=512, vocab_size=512,
        pattern=(LayerKind("attn", "dense"),), dtype="bfloat16")
    slots, cache_len, layout = 4, 8192, PagedLayout(128, 4 * 64 + 3)
    params = jax.eval_shape(lambda: model_api.init_model(
        jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: model_api.init_cache(
        None, cfg, slots, cache_len, paged=layout))
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        t)
    pools = {x.shape for p, x in jax.tree_util.tree_leaves_with_path(cache)
             if str(getattr(p[-1], "key", "")).endswith("_pages")}
    assert pools == {(3, 259, 128, 256)}
    shapes = pools | {s[1:] for s in pools}

    def fused(p, c, t, s):
        with decode_kernel_override("flash"):
            return model_api.decode_many(p, c, cfg, t, s, 4)
    # the cache donated, as the engine's window is: an undonated one is
    # copied once into the buffer the window then updates in place
    hlo = jax.jit(fused, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache),
        *on_chip((jax.ShapeDtypeStruct((slots, 1), jnp.int32),
                  jax.ShapeDtypeStruct((slots,), jnp.int32)))
    ).compile().as_text()
    assert "_paged_flash_call" in hlo
    found = pool_moves_in_hlo(hlo, shapes)
    assert not found, found


#: DeepSeek-V2-Lite's held experts: 16 x (2048 -> 1408 -> 2048) SwiGLU,
#: stacked over its 26 MoE layers
MOE_WIDTHS = (16, 2048, 1408)
MOE_LAYERS = 26


def _moe_gmm(one_chip, rows=624, tm=16):
    """The grouped expert kernel at published widths, as the layer scan
    calls it (the experts of every MoE layer stacked, read at a layer
    index): rows of a 64-slot decode step (384 assignments padded per
    expert to 16-row tiles)."""
    h, d, f = MOE_WIDTHS
    dt = jnp.bfloat16
    s = lambda shape, dtype=dt: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    tf = _ffn_tile(d, f, 2)
    return _compile(lambda g, u, layer, x, wi, wg, wo: _moe_gmm_call(
        g, u, layer, x, wi, wg, wo, tm=tm, tf=tf, interpret=False),
        s((rows // tm,), jnp.int32), s((1,), jnp.int32), s((1,), jnp.int32),
        s((rows, d)), s((MOE_LAYERS, h, d, f)), s((MOE_LAYERS, h, d, f)),
        s((MOE_LAYERS, h, f, d)))


@pytest.mark.parametrize("rows,tm", [(624, 16), (8176, 128)])
def test_moe_gmm_compiles(one_chip, rows, tm):
    """A decode step's rows (tiles of 16) and a 1024-token prefill's
    (tiles of 128), the whole FFN width in one block."""
    assert _ffn_tile(*MOE_WIDTHS[1:], 2) == MOE_WIDTHS[2]
    _moe_gmm(one_chip, rows, tm)


def test_moe_decode_window_copies_no_experts(one_chip, monkeypatch):
    """The fused decode window of a small MoE model, its routed experts
    through the compiled ``moe_gmm``: the optimized TPU HLO holds no copy,
    slice or reshape of one layer's experts or of the stacked experts (the
    layer scan passes them whole, read at the layer index)."""
    from repro.configs.base import LayerKind, ModelConfig
    from repro.kernels import moe_gmm as gmm_mod
    from repro.models import api as model_api
    from repro.models import moe as moe_mod
    from test_kv_pool_in_place import pool_moves_in_hlo
    monkeypatch.setattr(moe_mod, "moe_gmm", lambda *a, **k: gmm_mod.moe_gmm(
        *a, kernel=True, interpret=False, **k))
    cfg = ModelConfig(
        arch="moe-tpu", family="moe", n_layers=3, d_model=1024, n_heads=8,
        n_kv_heads=8, head_dim=128, d_ff=1024, d_ff_expert=1408,
        vocab_size=512, n_experts=32, top_k=6, experts_held=8,
        pattern=(LayerKind("attn", "moe"),), dtype="bfloat16")
    slots, cache_len = 8, 256
    params = jax.eval_shape(lambda: model_api.init_model(
        jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: model_api.init_cache(
        None, cfg, slots, cache_len))
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        t)
    experts = {x.shape for p, x in jax.tree_util.tree_leaves_with_path(params)
               if str(getattr(p[-1], "key", "")) in ("wi", "wg", "wo")
               and len(x.shape) == 4}
    assert experts == {(3, 8, 1024, 1408), (3, 8, 1408, 1024)}
    # one layer's experts, alone or as a stack of one
    shapes = experts | {s[1:] for s in experts} | {(1,) + s[1:]
                                                    for s in experts}
    hlo = jax.jit(lambda p, c, t, s: model_api.decode_many(
        p, c, cfg, t, s, 4), donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache),
        *on_chip((jax.ShapeDtypeStruct((slots, 1), jnp.int32),
                  jax.ShapeDtypeStruct((slots,), jnp.int32)))
    ).compile().as_text()
    assert "_moe_gmm_call" in hlo
    found = pool_moves_in_hlo(hlo, shapes)
    assert not found, found


def _needles(family):
    """``KERNELS`` of ``bench/families/<family>.py``: per roofline, the
    string it looks for in a device op's own name."""
    with open(os.path.join(ROOT, "bench", "families", family + ".py")) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
                getattr(t, "id", None) == "KERNELS" for t in node.targets):
            return ast.literal_eval(node.value)
    raise AssertionError(f"bench/families/{family}.py has no KERNELS")


def _custom_call_names(compiled):
    """Own names of the compiled custom calls, numbering dropped:
    '%_plan_dds_call.1 = bf16[...] custom-call(...)' -> '_plan_dds_call'."""
    return {re.sub(r"\.\d+$", "", m) for m in re.findall(
        r"%([\w.-]+) = [^\n]*custom-call\(", compiled.as_text())}


@pytest.mark.parametrize("family,kernel,compile_fn", [
    ("bert", "sparse_matmul", _plan_dds),
    ("lm", "sparse_matmul", _plan_dds),
    ("lm", "decode_attn", _paged_flash_decode),
    ("mla_moe", "sparse_matmul", _plan_dds),
    ("mla_moe", "moe_gmm", _moe_gmm),
])
def test_kernel_names_carry_the_benchmark_needles(one_chip, family, kernel,
                                                  compile_fn):
    """A rename of ``_plan_dds_call``, ``_paged_flash_call`` or
    ``_moe_gmm_call`` would leave the rooflines nothing to read: the
    benchmark finds each kernel by a string its op name contains."""
    needle = _needles(family)[kernel]
    names = _custom_call_names(compile_fn(one_chip))
    assert any(needle in n for n in names), (needle, names)
