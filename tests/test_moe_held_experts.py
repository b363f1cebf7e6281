"""The drop-free MoE layer over the experts a device holds, its grouped
expert matmul, and YaRN rope (DeepSeek-V2-Lite).

* shares: the parts of the layer that each expert-parallel share computes,
  with the shared experts (which every chip computes alike) counted once,
  add up to the uncut layer;
* batch independence: a token's routed output is the same, bit for bit,
  alone and in a batch, through the XLA path and the Pallas kernel
  (interpret mode);
* the Pallas kernel equals the XLA path, padding tiles and empty experts
  included;
* YaRN frequencies and scales equal a direct transcription of the
  published DeepSeek-V2 formulas.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import YarnRope
from repro.configs.registry import get_config
from repro.kernels.moe_gmm import group_layout, moe_gmm
from repro.models.common import apply_mlp, apply_rope, rope_freqs
from repro.models.mla import softmax_scale
from repro.models.moe import apply_moe, init_moe

RNG = np.random.RandomState(11)


def _cfg(n_experts, top_k, **kw):
    """V2-Lite's layer at a small width with ``n_experts`` routed."""
    return dataclasses.replace(get_config("deepseek_v2_lite_16b", smoke=True),
                               n_experts=n_experts, top_k=top_k, **kw)


def _x(cfg, b=2, s=24):
    return jnp.asarray(RNG.randn(b, s, cfg.d_model).astype(np.float32))


@pytest.mark.parametrize("n_experts,top_k,shares", [
    (64, 6, 4),       # the deployment: four chips of 16 experts, top-6
    (8, 2, 4),        # the smoke config: four shares of 2
    (8, 2, 2),
])
def test_shares_add_up_to_the_uncut_layer(n_experts, top_k, shares):
    whole = _cfg(n_experts, top_k)
    p = init_moe(jax.random.PRNGKey(3), whole)
    x = _x(whole)
    want, _, rows_all = apply_moe(p, x, whole)
    held = n_experts // shares
    got = apply_mlp(p["shared"], x, whole.act).astype(jnp.float32)
    rows = []
    for c in range(shares):
        cfg = dataclasses.replace(whole, experts_held=held,
                                  expert_offset=c * held)
        part = {"router": p["router"],
                **{w: p[w][c * held:(c + 1) * held]
                   for w in ("wi", "wg", "wo")}}
        y, _, r = apply_moe(part, x, cfg)
        got = got + y.astype(jnp.float32)
        rows.append(r)
    # float32 throughout: only the order of the sum over experts differs
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.concatenate(rows), rows_all)
    assert int(rows_all.sum()) == x.shape[0] * x.shape[1] * top_k


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_token_output_does_not_depend_on_its_batch(kernel, monkeypatch):
    import repro.models.moe as moe_mod
    monkeypatch.setattr(moe_mod, "moe_gmm",
                        lambda *a, **k: moe_gmm(*a, kernel=kernel, **k))
    cfg = _cfg(64, 6, experts_held=16, expert_offset=16)
    p = init_moe(jax.random.PRNGKey(4), cfg)
    routed = {k: v for k, v in p.items() if k != "shared"}
    x = _x(cfg, b=3, s=8)
    batch, _, _ = apply_moe(routed, x, cfg)
    whole, _, _ = apply_moe(p, x, cfg)
    for b, s in ((0, 0), (1, 5), (2, 7)):
        # the routed experts: bit for bit
        alone, _, _ = apply_moe(routed, x[b:b + 1, s:s + 1], cfg)
        np.testing.assert_array_equal(np.asarray(alone[0, 0]),
                                      np.asarray(batch[b, s]))
        # with the shared experts, a dense matmul whose float32 rounding on
        # the CPU backend depends on the number of rows
        alone, _, _ = apply_moe(p, x[b:b + 1, s:s + 1], cfg)
        np.testing.assert_allclose(np.asarray(alone[0, 0]),
                                   np.asarray(whole[b, s]), rtol=1e-5,
                                   atol=1e-7)
    # and a token masked out (padding, an idle slot) routes nowhere
    valid = jnp.ones(x.shape[:2], bool).at[1].set(False)
    y, _, rows = apply_moe(routed, x, cfg, valid=valid)
    np.testing.assert_array_equal(np.asarray(y[0]), np.asarray(batch[0]))
    assert not np.asarray(y[1]).any()
    _, _, rows0 = apply_moe(routed, x[:1], cfg)
    _, _, rows2 = apply_moe(routed, x[2:], cfg)
    np.testing.assert_array_equal(rows, rows0 + rows2)


@pytest.mark.parametrize("tm", [16, 32])
def test_kernel_matches_xla_path(tm):
    h, d, f, a = 4, 128, 256, 40
    groups = jnp.asarray(RNG.randint(0, h + 1, (a,)), jnp.int32)
    groups = groups.at[:5].set(2)          # group 1 may stay empty
    groups = jnp.where(groups == 1, h, groups)
    lay = group_layout(groups, h, tm, a)
    # every kept assignment has its own row, in its own group's tiles
    dest = np.asarray(lay.dest)
    kept = np.asarray(groups) < h
    assert len(set(dest[kept])) == kept.sum()
    assert (dest[~kept] == lay.row_src.shape[0]).all()
    tiles = np.asarray(lay.group)[dest[kept] // tm]
    np.testing.assert_array_equal(tiles, np.asarray(groups)[kept])
    w = lambda *s: jnp.asarray(RNG.randn(*s).astype(np.float32) * 0.1)
    x = jnp.take(w(a, d), lay.row_src, axis=0, mode="fill", fill_value=0)
    wi, wg, wo = w(h, d, f), w(h, d, f), w(h, f, d)
    want = moe_gmm(x, wi, wg, wo, lay, kernel=False)
    got = moe_gmm(x, wi, wg, wo, lay, kernel=True, interpret=True)
    used = int(lay.used[0]) * tm
    # float32 both ways; the two matmul paths round their sums differently
    np.testing.assert_allclose(np.asarray(got)[:used],
                               np.asarray(want)[:used], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kernel", [False, True])
def test_stacked_experts_read_at_the_layer_index(kernel):
    """Experts stacked over layers and read at a traced layer index give
    that layer's result, on both paths (the layer scan's call)."""
    n_layers, h, d, f, a, tm = 3, 4, 128, 256, 24, 16
    groups = jnp.asarray(RNG.randint(0, h + 1, (a,)), jnp.int32)
    lay = group_layout(groups, h, tm, a)
    w = lambda *s: jnp.asarray(RNG.randn(*s).astype(np.float32) * 0.1)
    x = jnp.take(w(a, d), lay.row_src, axis=0, mode="fill", fill_value=0)
    wi, wg = w(n_layers, h, d, f), w(n_layers, h, d, f)
    wo = w(n_layers, h, f, d)
    used = int(lay.used[0]) * tm
    run = jax.jit(lambda layer: moe_gmm(x, wi, wg, wo, lay, layer=layer,
                                        kernel=kernel, interpret=True))
    for layer in range(n_layers):
        want = moe_gmm(x, wi[layer], wg[layer], wo[layer], lay, kernel=False)
        # float32 both ways; the two matmul paths round their sums
        # differently
        np.testing.assert_allclose(np.asarray(run(layer))[:used],
                                   np.asarray(want)[:used],
                                   rtol=1e-5, atol=1e-5)


def _hf_yarn(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepSeek-V2's DeepseekV2YarnRotaryEmbedding frequencies, as
    published (modeling_deepseek.py), in numpy."""
    def find_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32)
                                 / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float32) / dim))
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def _hf_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


@pytest.mark.parametrize("dim,orig,mscale", [(64, 4096, 0.707),
                                             (8, 16, 0.707),
                                             (64, 4096, 1.0)])
def test_yarn_matches_published_formulas(dim, orig, mscale):
    y = YarnRope(factor=40.0, original_max_position=orig, beta_fast=32.0,
                 beta_slow=1.0, mscale=mscale, mscale_all_dim=0.707)
    inv, rot = rope_freqs(dim, theta=10000.0, yarn=y)
    assert rot == dim
    np.testing.assert_allclose(np.asarray(inv),
                               _hf_yarn(dim, 10000.0, 40.0, orig, 32.0, 1.0),
                               rtol=1e-6)
    # cos and sin carry mscale / mscale_all_dim: 1 when they are equal
    m = _hf_mscale(40.0, mscale) / _hf_mscale(40.0, 0.707)
    pos = jnp.arange(5)
    x = jnp.asarray(RNG.randn(5, 1, dim).astype(np.float32))
    ang = np.arange(5)[:, None] * _hf_yarn(dim, 10000.0, 40.0, orig, 32.0,
                                            1.0)
    cos, sin = np.cos(ang)[:, None] * m, np.sin(ang)[:, None] * m
    x1, x2 = np.asarray(x)[..., :dim // 2], np.asarray(x)[..., dim // 2:]
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_allclose(np.asarray(apply_rope(x, pos, yarn=y)), want,
                               rtol=1e-5, atol=1e-6)
    # the softmax scale: q_head_dim^-0.5 * mscale(factor, all_dim)^2
    cfg = get_config("deepseek_v2_lite_16b")
    assert math.isclose(softmax_scale(cfg), 192 ** -0.5 * (
        0.1 * 0.707 * math.log(40) + 1) ** 2, rel_tol=1e-12)
