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


def _paged_flash_decode(one_chip):
    b, hkv, g, d, ps, npg, dt = 4, 4, 2, 256, 16, 64, jnp.bfloat16
    n_pages = b * npg
    s = lambda shape, dtype=dt: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    return _compile(lambda q, kp, vp, pt, pm, pos: paged_flash_decode(
        q, kp, vp, pt, pm, pos, interpret=False),
        s((b, 1, hkv * g, d)), s((n_pages, ps, hkv, d)),
        s((n_pages, ps, hkv, d)), s((b, npg), jnp.int32),
        s((b, npg * ps), jnp.int32), s((b,), jnp.int32))


def test_paged_flash_decode_compiles(one_chip):
    _paged_flash_decode(one_chip)


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
])
def test_kernel_names_carry_the_benchmark_needles(one_chip, family, kernel,
                                                  compile_fn):
    """A rename of ``_plan_dds_call`` or ``_paged_flash_call`` would leave
    the rooflines nothing to read: the benchmark finds each kernel by a
    string its op name contains."""
    needle = _needles(family)[kernel]
    names = _custom_call_names(compile_fn(one_chip))
    assert any(needle in n for n in names), (needle, names)
