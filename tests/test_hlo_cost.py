"""Loop-aware HLO cost model: exact match vs XLA on loop-free graphs,
trip-count scaling on loops, collective accounting under SPMD."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.hlo_cost import HloCostModel, parse_module
from repro.launch.mesh import make_mesh


def _compile(fn, *specs, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*specs).compile()


def test_matches_xla_on_loop_free_graph():
    def fn(a, b):
        return jnp.tanh(a @ b) @ b
    c = _compile(fn, jax.ShapeDtypeStruct((256, 512), jnp.float32),
                 jax.ShapeDtypeStruct((512, 512), jnp.float32))
    ours = HloCostModel(c.as_text()).total()
    xla = c.cost_analysis()
    if isinstance(xla, list):   # older JAX returns one dict per partition
        xla = xla[0]
    assert abs(ours.flops / xla["flops"] - 1) < 0.02
    assert abs(ours.bytes / xla["bytes accessed"] - 1) < 0.05


def test_scales_with_trip_count():
    def make(n):
        def fn(h):
            out, _ = jax.lax.scan(lambda h, _: (jnp.tanh(h @ h), None), h,
                                  None, length=n)
            return out
        c = _compile(fn, jax.ShapeDtypeStruct((128, 128), jnp.float32))
        return HloCostModel(c.as_text()).total().flops
    f3, f12 = make(3), make(12)
    assert abs(f12 / f3 - 4.0) < 0.1
    # absolute: one body dot = 2*128^3
    assert abs(f3 / (3 * 2 * 128 ** 3) - 1) < 0.1


def test_nested_loops_multiply():
    def fn(h):
        def outer(h, _):
            def inner(h, _):
                return jnp.tanh(h @ h), None
            h, _ = jax.lax.scan(inner, h, None, length=5)
            return h, None
        out, _ = jax.lax.scan(outer, h, None, length=3)
        return out
    c = _compile(fn, jax.ShapeDtypeStruct((64, 64), jnp.float32))
    flops = HloCostModel(c.as_text()).total().flops
    assert abs(flops / (15 * 2 * 64 ** 3) - 1) < 0.1


def test_collectives_counted_with_trip_multiplier():
    mesh = make_mesh((1,), ("d",))
    sh = NamedSharding(mesh, P("d"))

    def fn(x):
        def body(c, _):
            s = jax.lax.with_sharding_constraint(c, sh)
            return jnp.tanh(s @ s.T @ s), None
        out, _ = jax.lax.scan(body, x, None, length=4)
        return jnp.sum(out)
    c = _compile(fn, jax.ShapeDtypeStruct((8, 8), jnp.float32))
    m = HloCostModel(c.as_text())
    # on a 1-device mesh there may be no collectives; the parse must at
    # least succeed and produce finite totals
    t = m.total()
    assert np.isfinite(t.flops) and np.isfinite(t.bytes)


def test_parser_handles_tuple_types_with_index_comments():
    text = """HloModule m, is_scheduled=true

%body (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %p = (s32[], /*index=1*/ f32[4,4]{1,0}) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %g1 = f32[4,4]{1,0} get-tuple-element(%p), index=1
  %c1 = s32[] constant(1)
  %a = s32[] add(%g0, %c1)
  %d = f32[4,4]{1,0} dot(%g1, %g1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], /*index=1*/ f32[4,4]{1,0}) tuple(%a, %d)
}

%cond (p2: (s32[], f32[4,4])) -> pred[] {
  %p2 = (s32[], /*index=1*/ f32[4,4]{1,0}) parameter(0)
  %g = s32[] get-tuple-element(%p2), index=0
  %c = s32[] constant(10)
  ROOT %lt = pred[] compare(%g, %c), direction=LT
}

ENTRY %main (x: f32[4,4]) -> f32[4,4] {
  %x = f32[4,4]{1,0} parameter(0)
  %z = s32[] constant(0)
  %tup = (s32[], /*index=1*/ f32[4,4]{1,0}) tuple(%z, %x)
  %w = (s32[], /*index=1*/ f32[4,4]{1,0}) while(%tup), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %out = f32[4,4]{1,0} get-tuple-element(%w), index=1
}
"""
    m = HloCostModel(text)
    comps, entry = parse_module(text)
    assert entry == "main"
    t = m.total()
    dot_flops = 10 * 2 * 4 * 4 * 4          # 10 trips x dot(4x4x4)
    assert dot_flops <= t.flops <= dot_flops + 10 * 4  # + add/compare per trip


def test_roofline_peaks_keyed_by_device_kind():
    from repro.launch.hlo_analysis import PEAKS, Roofline
    r = Roofline(197e12, 819e9, 0.0, 1, device_kind="TPU v5 lite")
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(1.0)
    assert r.to_dict()["device_kind"] == "TPU v5 lite"
    assert set(PEAKS["TPU v5 lite"]) == {"flops", "hbm_bw", "link_bw"}
    with pytest.raises(ValueError, match="no published peaks"):
        Roofline(1.0, 1.0, 0.0, 1, device_kind="cpu")
