"""Per-arch smoke tests (reduced configs, one forward/train step, shape +
finiteness) and model-math equivalences (flash==full, local==masked-full,
SSD chunked==recurrence, decode==forward)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, get_config
from repro.launch.steps import make_train_step
from repro.models import decode_step, init_cache, init_model, model_forward
from repro.models.attention import (decode_attention, flash_attention,
                                    full_attention, local_attention)
from repro.optim.adamw import AdamWConfig, init_opt_state

RNG = np.random.RandomState(0)


def _batch(cfg, b=2, s=32, labels=True):
    out = {"tokens": jnp.asarray(RNG.randint(0, cfg.vocab_size, (b, s)))}
    if labels:
        out["labels"] = jnp.asarray(RNG.randint(0, cfg.vocab_size, (b, s)))
    if cfg.family == "audio":
        out["frames"] = jnp.asarray(
            RNG.randn(b, cfg.n_audio_ctx, cfg.d_model).astype(np.float32))
    if cfg.family == "vlm":
        out["mm_embeds"] = jnp.asarray(
            RNG.randn(b, cfg.n_patches, cfg.d_model).astype(np.float32))
    return out


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward(arch):
    cfg = get_config(arch, smoke=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    logits, aux = model_forward(params, cfg, batch)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ["deepseek_7b", "qwen3_moe_235b_a22b",
                                  "mamba2_780m", "recurrentgemma_9b",
                                  "bert_base"])
def test_arch_smoke_train_step(arch):
    cfg = get_config(arch, smoke=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    opt_cfg = AdamWConfig(peak_lr=1e-3, warmup_steps=1, total_steps=10)
    opt = init_opt_state(params, opt_cfg)
    step = jax.jit(make_train_step(cfg, opt_cfg))
    batch = _batch(cfg)
    params2, opt2, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    # params actually moved
    delta = jax.tree_util.tree_reduce(
        lambda a, l: a + float(jnp.abs(l).sum()),
        jax.tree_util.tree_map(jnp.subtract, params2, params), 0.0)
    assert delta > 0


@pytest.mark.parametrize("arch", ["deepseek_7b", "gemma3_4b",
                                  "mamba2_780m", "recurrentgemma_9b"])
def test_decode_matches_forward(arch):
    cfg = get_config(arch, smoke=True)
    params = init_model(jax.random.PRNGKey(1), cfg)
    b, s = 2, 40
    toks = RNG.randint(0, cfg.vocab_size, (b, s))
    logits_fwd, _ = model_forward(params, cfg, {"tokens": jnp.asarray(toks)})
    cache = init_cache(params, cfg, b, s)
    step = jax.jit(lambda c, tok, pos: decode_step(params, c, cfg, tok,
                                                   pos)[:2])
    errs = []
    for t in range(s):
        lg, cache = step(cache, jnp.asarray(toks[:, t:t + 1]), jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - logits_fwd[:, t]))))
    assert max(errs) < 2e-3, max(errs)


class TestAttentionEquivalence:
    def _qkv(self, b=2, s=256, hq=4, hkv=2, d=32):
        q = jnp.asarray(RNG.randn(b, s, hq, d).astype(np.float32))
        k = jnp.asarray(RNG.randn(b, s, hkv, d).astype(np.float32))
        v = jnp.asarray(RNG.randn(b, s, hkv, d).astype(np.float32))
        return q, k, v

    def test_flash_equals_full_causal(self):
        q, k, v = self._qkv()
        a = full_attention(q, k, v, causal=True)
        f = flash_attention(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
        np.testing.assert_allclose(np.asarray(f), np.asarray(a), atol=2e-5)

    def test_flash_equals_full_windowed(self):
        q, k, v = self._qkv()
        a = full_attention(q, k, v, causal=True, window=96)
        f = flash_attention(q, k, v, causal=True, window=96,
                            q_chunk=64, kv_chunk=32)
        np.testing.assert_allclose(np.asarray(f), np.asarray(a), atol=2e-5)

    def test_local_equals_full_windowed(self):
        q, k, v = self._qkv(s=256)
        for w in (32, 64, 128):
            a = full_attention(q, k, v, causal=True, window=w)
            l = local_attention(q, k, v, window=w)
            np.testing.assert_allclose(np.asarray(l), np.asarray(a),
                                       atol=2e-5, err_msg=f"window={w}")

    def test_flash_noncausal(self):
        q, k, v = self._qkv()
        a = full_attention(q, k, v, causal=False)
        f = flash_attention(q, k, v, causal=False, q_chunk=64, kv_chunk=64)
        np.testing.assert_allclose(np.asarray(f), np.asarray(a), atol=2e-5)

    def test_decode_ring_cache_matches_window(self):
        """Ring-buffered local cache == full recompute with window mask."""
        b, s, hq, hkv, d, w = 1, 48, 2, 1, 16, 16
        q_all = RNG.randn(b, s, hq, d).astype(np.float32)
        k_all = RNG.randn(b, s, hkv, d).astype(np.float32)
        v_all = RNG.randn(b, s, hkv, d).astype(np.float32)
        kc = jnp.zeros((b, w, hkv, d))
        vc = jnp.zeros((b, w, hkv, d))
        pm = jnp.full((w,), -1, jnp.int32)
        dec = jax.jit(lambda q, kc, vc, pm, t: decode_attention(
            q, kc, vc, pm, t, window=w))
        for t in range(s):
            slot = t % w
            kc = kc.at[:, slot].set(k_all[:, t])
            vc = vc.at[:, slot].set(v_all[:, t])
            pm = pm.at[slot].set(t)
            got = dec(jnp.asarray(q_all[:, t:t + 1]), kc, vc, pm, t)
            ref = full_attention(jnp.asarray(q_all[:, t:t + 1]),
                                 jnp.asarray(k_all[:, :t + 1]),
                                 jnp.asarray(v_all[:, :t + 1]),
                                 causal=True, window=w, q_offset=t)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       atol=1e-5, err_msg=f"t={t}")


def test_ssd_chunked_equals_recurrence():
    """Chunked SSD == step-by-step linear recurrence."""
    from repro.models.ssm import _ssd_chunked
    b, s, h, p, n = 1, 64, 2, 4, 8
    x = RNG.randn(b, s, h, p).astype(np.float32)
    dt = np.abs(RNG.randn(b, s, h)).astype(np.float32) * 0.5
    a_neg = -np.abs(RNG.randn(h)).astype(np.float32)
    da = dt * a_neg[None, None, :]
    bm = RNG.randn(b, s, n).astype(np.float32)
    cm = RNG.randn(b, s, n).astype(np.float32)
    y = np.asarray(_ssd_chunked(jnp.asarray(x), jnp.asarray(dt),
                                jnp.asarray(da), jnp.asarray(bm),
                                jnp.asarray(cm), chunk=16))
    # reference recurrence
    state = np.zeros((b, h, p, n), np.float32)
    ref = np.zeros_like(y)
    for t in range(s):
        decay = np.exp(da[:, t])[..., None, None]
        upd = np.einsum("bh,bhp,bn->bhpn", dt[:, t], x[:, t], bm[:, t])
        state = state * decay + upd
        ref[:, t] = np.einsum("bhpn,bn->bhp", state, cm[:, t])
    np.testing.assert_allclose(y, ref, rtol=1e-3, atol=1e-3)


def test_moe_capacity_drops_only_overflow():
    """The MoE layer has no capacity, so it drops nothing: every token's
    output is its own, and shuffling the tokens shuffles y."""
    cfg = get_config("qwen3_moe_235b_a22b", smoke=True)
    from repro.models.moe import apply_moe, init_moe
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(RNG.randn(2, 16, cfg.d_model).astype(np.float32))
    y, aux, rows = apply_moe(p, x, cfg)
    assert y.shape == x.shape and bool(jnp.all(jnp.isfinite(y)))
    assert int(rows.sum()) == 2 * 16 * cfg.top_k
    perm = RNG.permutation(16)
    y2, _, _ = apply_moe(p, x[:, perm], cfg)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y[:, perm]),
                               rtol=2e-3, atol=2e-3)


def test_int8_kv_cache_decode_close_to_exact():
    """Quantized decode cache (capacity fix, §Perf iter 5): logits within
    ~1% relative of the full-precision forward."""
    import dataclasses
    cfg0 = get_config("deepseek_7b", smoke=True)
    cfgq = dataclasses.replace(cfg0, kv_cache_quant=True)
    params = init_model(jax.random.PRNGKey(0), cfg0)
    b, s = 2, 32
    toks = RNG.randint(0, cfg0.vocab_size, (b, s))
    logits_fwd, _ = model_forward(params, cfg0, {"tokens": jnp.asarray(toks)})
    cache = init_cache(params, cfgq, b, s)
    step = jax.jit(lambda c, tok, pos: decode_step(params, c, cfgq, tok,
                                                   pos)[:2])
    errs = []
    for t in range(s):
        lg, cache = step(cache, jnp.asarray(toks[:, t:t + 1]), jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - logits_fwd[:, t]))))
    rel = max(errs) / float(jnp.abs(logits_fwd).max())
    assert rel < 0.02, rel
