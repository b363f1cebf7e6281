"""Fused multi-token decode (models.api.decode_many + engine sync_every):
the K-step on-device loop must reproduce the per-step loop exactly --
token-for-token -- for every decode-capable mixer family, through mixed
prompt lengths, EOS mid-window, slot recycling at sync boundaries, and
seeded temperature/top-k sampling (same base key => identical tokens
between fused and unfused paths; models/sampling.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LayerKind, ModelConfig
from repro.configs.registry import get_config
from repro.models import api as model_api
from repro.models import init_model
from repro.models.sampling import sample_tokens
from repro.serving import ServingSpec, prepare_servable

RNG = np.random.RandomState(0)

ATTN_TARGETS = ("attn/wq", "attn/wk", "attn/wv", "attn/wo")


def _servable(cfg, seed=1, sparsity=0.5):
    params = init_model(jax.random.PRNGKey(seed), cfg)
    return prepare_servable(params, cfg, ServingSpec(
        tile=(16, 16), sparsity=sparsity, prune="oneshot",
        targets=ATTN_TARGETS))


def _mla_dense_cfg():
    return ModelConfig(
        arch="mla-dense-smoke", family="dense",
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, vocab_size=512,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        pattern=(LayerKind("mla", "dense"),), dtype="float32")


def _run_engine(servable, prompts, max_new, sync_every, *, cache_len=64,
                max_slots=None, frames=None, **engine_kw):
    eng = servable.engine(max_slots=max_slots or len(prompts),
                          cache_len=cache_len, sync_every=sync_every,
                          **engine_kw)
    handles = [eng.submit(p, max_new_tokens=max_new,
                          frames=None if frames is None else frames[i])
               for i, p in enumerate(prompts)]
    eng.run()
    assert all(h.done for h in handles)
    return eng, handles


# --------------------------------------------------------------------------
# fused == per-step, per family (mixed lengths + recycling at sync points)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["deepseek_7b", "mamba2_780m",
                                  "recurrentgemma_9b"])
def test_fused_matches_per_step(arch):
    """6 mixed-length requests through 2 slots: admission, fused windows,
    mid-window completion, slot recycling at sync boundaries -- all token
    streams must equal the per-step engine's."""
    cfg = get_config(arch, smoke=True)
    servable = _servable(cfg)
    prompts = [RNG.randint(0, cfg.vocab_size, (L,)).tolist()
               for L in (3, 11, 7, 5, 9, 4)]
    _, ref = _run_engine(servable, prompts, 6, 1, max_slots=2)
    eng, got = _run_engine(servable, prompts, 6, 4, max_slots=2)
    for h_ref, h_got in zip(ref, got):
        assert h_got.tokens == h_ref.tokens
    # the fused engine really fused: fewer dispatches than decode steps
    assert eng.stats.windows < eng.stats.steps


def test_fused_matches_per_step_mla():
    cfg = _mla_dense_cfg()
    servable = _servable(cfg)
    prompts = [RNG.randint(0, cfg.vocab_size, (L,)).tolist()
               for L in (4, 9, 13)]
    _, ref = _run_engine(servable, prompts, 5, 1)
    _, got = _run_engine(servable, prompts, 5, 8)
    for h_ref, h_got in zip(ref, got):
        assert h_got.tokens == h_ref.tokens


def test_fused_matches_per_step_moe_high_capacity():
    cfg = get_config("deepseek_v2_lite_16b", smoke=True)
    servable = _servable(cfg)
    prompts = [RNG.randint(0, cfg.vocab_size, (L,)).tolist() for L in (3, 8)]
    _, ref = _run_engine(servable, prompts, 4, 1, cache_len=32)
    _, got = _run_engine(servable, prompts, 4, 4, cache_len=32)
    for h_ref, h_got in zip(ref, got):
        assert h_got.tokens == h_ref.tokens


def test_fused_matches_per_step_audio():
    cfg = get_config("whisper_base", smoke=True)
    params = init_model(jax.random.PRNGKey(3), cfg)
    servable = prepare_servable(params, cfg, ServingSpec(tile=(16, 16)))
    frames = [RNG.randn(cfg.n_audio_ctx, cfg.d_model).astype(np.float32)
              for _ in range(3)]
    prompts = [RNG.randint(0, cfg.vocab_size, (L,)).tolist()
               for L in (2, 6, 4)]
    _, ref = _run_engine(servable, prompts, 4, 1, cache_len=32,
                         frames=frames)
    _, got = _run_engine(servable, prompts, 4, 4, cache_len=32,
                         frames=frames)
    for h_ref, h_got in zip(ref, got):
        assert h_got.tokens == h_ref.tokens


def test_eos_mid_window():
    """EOS sampled inside a fused window must stop that slot exactly there
    (emitted tokens cut at the EOS token) while co-resident slots run on."""
    cfg = get_config("deepseek_7b", smoke=True)
    servable = _servable(cfg)
    prompts = [RNG.randint(0, cfg.vocab_size, (L,)).tolist() for L in (3, 7)]
    _, ref = _run_engine(servable, prompts, 8, 1)
    eos = ref[0].tokens[2]      # forces a stop 3 tokens in, mid-window
    eng1 = servable.engine(max_slots=2, cache_len=64, sync_every=1)
    a1 = eng1.submit(prompts[0], max_new_tokens=8, eos_id=eos)
    b1 = eng1.submit(prompts[1], max_new_tokens=8)
    eng1.run()
    eng8 = servable.engine(max_slots=2, cache_len=64, sync_every=8)
    a8 = eng8.submit(prompts[0], max_new_tokens=8, eos_id=eos)
    b8 = eng8.submit(prompts[1], max_new_tokens=8)
    eng8.run()
    assert a8.tokens == a1.tokens and a8.tokens[-1] == eos
    assert len(a8.tokens) <= 4
    assert b8.tokens == b1.tokens and len(b8.tokens) == 8


# --------------------------------------------------------------------------
# model-level decode_many == decode_step loop (cache state included)
# --------------------------------------------------------------------------

def test_decode_many_equals_step_loop():
    cfg = get_config("deepseek_7b", smoke=True)
    params = init_model(jax.random.PRNGKey(5), cfg)
    b, k_steps = 3, 5
    tok0 = jnp.asarray(RNG.randint(0, cfg.vocab_size, (b, 1)), jnp.int32)
    pos0 = jnp.asarray([0, 3, -1], jnp.int32)   # mixed progress + inactive

    cache_a = model_api.init_cache(params, cfg, b, 32)
    cache_b = model_api.init_cache(params, cfg, b, 32)
    toks, valid, state = model_api.decode_many(
        params, cache_a, cfg, tok0, pos0, k_steps)

    tok, pos = tok0, pos0
    ref_toks = []
    for _ in range(k_steps):
        logits, cache_b, _ = model_api.decode_step(params, cache_b, cfg,
                                                   tok, pos)
        nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
        active = pos >= 0
        nxt = jnp.where(active, nxt, 0)
        ref_toks.append(np.asarray(nxt))
        pos = jnp.where(active, pos + 1, pos)
        tok = jnp.where(active, nxt, tok[:, 0])[:, None]

    np.testing.assert_array_equal(np.asarray(toks), np.stack(ref_toks))
    np.testing.assert_array_equal(np.asarray(valid),
                                  np.stack([[True, True, False]] * k_steps))
    # carried caches must be state-identical: one more step agrees <= 1e-5
    lg_a, _, _ = model_api.decode_step(params, state["cache"], cfg,
                                       state["token"], state["pos"])
    lg_b, _, _ = model_api.decode_step(params, cache_b, cfg, tok, pos)
    np.testing.assert_allclose(np.asarray(lg_a[:2]), np.asarray(lg_b[:2]),
                               atol=1e-5)


def test_decode_many_remaining_budget():
    """A slot whose budget runs out mid-window self-deactivates: exactly
    ``remaining`` tokens valid, pos -1 afterwards."""
    cfg = get_config("deepseek_7b", smoke=True)
    params = init_model(jax.random.PRNGKey(6), cfg)
    cache = model_api.init_cache(params, cfg, 2, 32)
    tok0 = jnp.asarray(RNG.randint(0, cfg.vocab_size, (2, 1)), jnp.int32)
    toks, valid, state = model_api.decode_many(
        params, cache, cfg, tok0, jnp.asarray([0, 0], jnp.int32), 6,
        remaining=jnp.asarray([2, 8], jnp.int32))
    v = np.asarray(valid)
    assert v[:, 0].sum() == 2 and v[:, 1].sum() == 6
    assert np.asarray(state["pos"])[0] == -1        # budget spent -> inactive
    assert np.asarray(state["pos"])[1] > 0          # budget left -> still live
    assert np.asarray(state["remaining"])[1] == 2


# --------------------------------------------------------------------------
# seeded sampling parity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("temperature,top_k", [(0.7, 0), (1.0, 5)])
def test_seeded_sampling_parity(temperature, top_k):
    """Same base seed => identical sampled continuations between the fused
    and per-step engines (slot+position-keyed PRNG), and a different seed
    actually changes them."""
    cfg = get_config("deepseek_7b", smoke=True)
    servable = _servable(cfg)
    prompts = [RNG.randint(0, cfg.vocab_size, (L,)).tolist()
               for L in (3, 11, 7)]
    kw = dict(temperature=temperature, top_k=top_k)
    _, ref = _run_engine(servable, prompts, 6, 1, seed=7, **kw)
    _, got = _run_engine(servable, prompts, 6, 4, seed=7, **kw)
    for h_ref, h_got in zip(ref, got):
        assert h_got.tokens == h_ref.tokens
    _, other = _run_engine(servable, prompts, 6, 4, seed=8, **kw)
    assert any(a.tokens != b.tokens for a, b in zip(got, other))


def test_servable_decode_many_public_api():
    """The non-donating Servable.decode_many: same contract as the model
    API, usable without an engine (docs/API.md)."""
    cfg = get_config("deepseek_7b", smoke=True)
    servable = _servable(cfg)
    cache = servable.init_cache(2, 32)
    tok = jnp.asarray(RNG.randint(0, cfg.vocab_size, (2, 1)), jnp.int32)
    toks, valid, state = servable.decode_many(
        cache, tok, jnp.asarray([0, 0], jnp.int32), 4)
    assert toks.shape == (4, 2) and bool(np.all(np.asarray(valid)))
    # the input cache was not donated: still usable
    logits, _ = servable.decode_step(cache, tok, jnp.int32(0))
    assert logits.shape == (2, 1, cfg.vocab_size)


def test_sample_tokens_greedy_and_topk():
    logits = jnp.asarray(RNG.randn(4, 32).astype(np.float32))
    pos = jnp.asarray([0, 1, 2, 3], jnp.int32)
    key = jax.random.PRNGKey(0)
    greedy = sample_tokens(logits, key, pos, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(greedy),
                                  np.argmax(np.asarray(logits), axis=-1))
    # top-k samples must come from the k largest entries per row
    k = 3
    sampled = np.asarray(sample_tokens(logits, key, pos, temperature=1.0,
                                       top_k=k))
    topk = np.argsort(np.asarray(logits), axis=-1)[:, -k:]
    for i in range(4):
        assert sampled[i] in topk[i]
