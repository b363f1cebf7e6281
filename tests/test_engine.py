"""Continuous-batching engine: batched ragged-slot decode equals sequential
per-request decode for every decode-capable mixer family (attention, MLA,
SSM, RG-LRU hybrid, enc-dec audio), slot recycling hygiene (a freed slot
serves the next request exactly like a fresh cache), and the ragged-pos
per-row causal/window mask semantics underneath it all."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LayerKind, ModelConfig
from repro.configs.registry import get_config
from repro.models import api as model_api
from repro.models import init_model
from repro.models.attention import decode_attention, full_attention
from repro.serving import ServingSpec, prepare_servable

RNG = np.random.RandomState(0)

ATTN_TARGETS = ("attn/wq", "attn/wk", "attn/wv", "attn/wo")


def _mla_dense_cfg():
    """MLA mixer + dense FFN: the absorbed-latent decode path alone."""
    return ModelConfig(
        arch="mla-dense-smoke", family="dense",
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, vocab_size=512,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        pattern=(LayerKind("mla", "dense"),), dtype="float32")


def _servable(cfg, seed=1, sparsity=0.5):
    params = init_model(jax.random.PRNGKey(seed), cfg)
    return prepare_servable(params, cfg, ServingSpec(
        tile=(16, 16), sparsity=sparsity, prune="oneshot",
        targets=ATTN_TARGETS))


def _sequential(servable, prompt, max_new, cache_len, frames=None):
    """B=1 reference: per-request prefill through the decode path, then
    greedy generation -- what the engine must reproduce under batching."""
    cache = servable.init_cache(1, cache_len, frames=frames)
    logits = None
    for t, tok in enumerate(prompt):
        logits, cache = servable.decode_step(
            cache, jnp.asarray([[tok]], jnp.int32), jnp.int32(t))
    toks, logs = [], []
    pos = len(prompt)
    cur = int(np.argmax(np.asarray(logits[0, 0])))
    toks.append(cur)
    logs.append(np.asarray(logits[0, 0], np.float32))
    while len(toks) < max_new:
        logits, cache = servable.decode_step(
            cache, jnp.asarray([[cur]], jnp.int32), jnp.int32(pos))
        pos += 1
        cur = int(np.argmax(np.asarray(logits[0, 0])))
        toks.append(cur)
        logs.append(np.asarray(logits[0, 0], np.float32))
    return toks, logs


# --------------------------------------------------------------------------
# ragged-pos mask semantics (the primitive under the engine)
# --------------------------------------------------------------------------

def test_ragged_pos_masks_match_per_row_reference():
    """decode_attention with a (B,T) pos_map + (B,) pos == per-row full
    attention at each row's own position (causal AND windowed)."""
    b, s, hq, hkv, d = 3, 24, 2, 1, 16
    q_all = RNG.randn(b, s, hq, d).astype(np.float32)
    k_all = RNG.randn(b, s, hkv, d).astype(np.float32)
    v_all = RNG.randn(b, s, hkv, d).astype(np.float32)
    pos = np.array([5, 17, 11], np.int32)       # ragged per-slot positions
    for window in (0, 8):
        t = s
        kc = jnp.asarray(k_all)
        vc = jnp.asarray(v_all)
        pm = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        q = jnp.asarray(q_all[np.arange(b), pos])[:, None]
        got = decode_attention(q, kc, vc, pm, jnp.asarray(pos), window=window)
        for i in range(b):
            ref = full_attention(jnp.asarray(q_all[i:i + 1, pos[i]:pos[i] + 1]),
                                 jnp.asarray(k_all[i:i + 1, :pos[i] + 1]),
                                 jnp.asarray(v_all[i:i + 1, :pos[i] + 1]),
                                 causal=True, window=window,
                                 q_offset=int(pos[i]))
            np.testing.assert_allclose(np.asarray(got[i]), np.asarray(ref[0]),
                                       atol=1e-5,
                                       err_msg=f"row={i} window={window}")


def test_inactive_rows_leave_cache_untouched():
    """pos = -1 rows (free slots / prefill padding) must not write KV or
    advance recurrent state, for every mixer kind."""
    cfg = get_config("recurrentgemma_9b", smoke=True)   # rglru + local attn
    params = init_model(jax.random.PRNGKey(0), cfg)
    cache = model_api.init_cache(params, cfg, 2, 32)
    tok = jnp.asarray(RNG.randint(0, cfg.vocab_size, (2, 1)))
    # row 0 active at pos 0, row 1 inactive
    _, cache1, _ = model_api.decode_step(params, cache, cfg, tok,
                                         jnp.asarray([0, -1], jnp.int32))
    row1_before = model_api.read_slot(cache, cfg, 1)
    row1_after = model_api.read_slot(cache1, cfg, 1)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        row1_before, row1_after)
    # ...and the active row did write something
    row0_delta = jax.tree_util.tree_reduce(
        lambda acc, x: acc + float(jnp.abs(x.astype(jnp.float32)).sum()),
        jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            model_api.read_slot(cache1, cfg, 0),
            model_api.read_slot(cache, cfg, 0)), 0.0)
    assert row0_delta > 0


def test_scalar_pos_broadcast_back_compat():
    """The single-request convention (scalar pos) still decodes exactly."""
    cfg = get_config("deepseek_7b", smoke=True)
    params = init_model(jax.random.PRNGKey(1), cfg)
    b, s = 2, 16
    toks = RNG.randint(0, cfg.vocab_size, (b, s))
    fwd, _ = model_api.model_forward(params, cfg,
                                     {"tokens": jnp.asarray(toks)})
    c_scalar = model_api.init_cache(params, cfg, b, s)
    c_vector = model_api.init_cache(params, cfg, b, s)
    for t in range(s):
        tok = jnp.asarray(toks[:, t:t + 1])
        lg_s, c_scalar, _ = model_api.decode_step(params, c_scalar, cfg,
                                                  tok, jnp.int32(t))
        lg_v, c_vector, _ = model_api.decode_step(
            params, c_vector, cfg, tok, jnp.full((b,), t, jnp.int32))
        np.testing.assert_array_equal(np.asarray(lg_s), np.asarray(lg_v))
        np.testing.assert_allclose(np.asarray(lg_s[:, 0]),
                                   np.asarray(fwd[:, t]), atol=2e-5)


@pytest.mark.parametrize("arch", ["deepseek_7b", "mamba2_780m",
                                  "recurrentgemma_9b"])
def test_one_pass_prefill_matches_sequential(arch):
    """prefill_cache (one forward pass, bulk cache writes, bucket padding,
    ring wrap for windowed layers) == token-by-token decode prefill."""
    cfg = get_config(arch, smoke=True)
    params = init_model(jax.random.PRNGKey(2), cfg)
    L, bucket, cache_len = 41, 64, 64       # > the 32-token smoke windows
    toks = RNG.randint(0, cfg.vocab_size, (1, L))
    padded = np.zeros((1, bucket), np.int64)
    padded[:, :L] = toks
    cache_ref = model_api.init_cache(params, cfg, 1, cache_len)
    for t in range(L):
        lg_ref, cache_ref, _ = model_api.decode_step(
            params, cache_ref, cfg, jnp.asarray(toks[:, t:t + 1]),
            jnp.int32(t))
    cache_pf = model_api.init_cache(params, cfg, 1, cache_len)
    lg_pf, cache_pf, _ = model_api.prefill_cache(
        params, cache_pf, cfg, jnp.asarray(padded), jnp.int32(L))
    np.testing.assert_allclose(np.asarray(lg_pf[:, L - 1]),
                               np.asarray(lg_ref[:, 0]), atol=1e-5)
    # both caches must continue identically
    cur = int(np.argmax(np.asarray(lg_ref[0, 0])))
    for t in range(L, L + 6):
        tok = jnp.asarray([[cur]], jnp.int32)
        lg_r, cache_ref, _ = model_api.decode_step(params, cache_ref, cfg,
                                                   tok, jnp.int32(t))
        lg_p, cache_pf, _ = model_api.decode_step(params, cache_pf, cfg, tok,
                                                  jnp.int32(t))
        np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_r),
                                   atol=1e-5)
        cur = int(np.argmax(np.asarray(lg_r[0, 0])))


# --------------------------------------------------------------------------
# engine batched decode == sequential per-request decode, per family
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch,mixer", [
    ("deepseek_7b", "attention"),
    ("mamba2_780m", "ssm"),
    ("recurrentgemma_9b", "rglru+local"),
])
def test_engine_matches_sequential(arch, mixer):
    cfg = get_config(arch, smoke=True)
    servable = _servable(cfg)
    prompts = [RNG.randint(0, cfg.vocab_size, (L,)).tolist()
               for L in (3, 11, 7, 5)]          # mixed lengths, all co-active
    eng = servable.engine(max_slots=4, cache_len=64, collect_logits=True)
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run()
    for h, p in zip(handles, prompts):
        want_toks, want_logs = _sequential(servable, p, 6, 64)
        assert h.done and h.tokens == want_toks, mixer
        for got, want in zip(h.step_logits, want_logs):
            np.testing.assert_allclose(got, want, atol=1e-5)


def test_engine_matches_sequential_mla():
    cfg = _mla_dense_cfg()
    servable = _servable(cfg)
    prompts = [RNG.randint(0, cfg.vocab_size, (L,)).tolist()
               for L in (4, 9, 13)]
    eng = servable.engine(max_slots=3, cache_len=64, collect_logits=True)
    handles = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run()
    for h, p in zip(handles, prompts):
        want_toks, want_logs = _sequential(servable, p, 5, 64)
        assert h.tokens == want_toks
        for got, want in zip(h.step_logits, want_logs):
            np.testing.assert_allclose(got, want, atol=1e-5)


def test_engine_matches_sequential_moe_high_capacity():
    """The MoE layer drops nothing and routes each token alone, so the
    batched engine matches sequential decode."""
    cfg = get_config("deepseek_v2_lite_16b", smoke=True)
    servable = _servable(cfg)
    prompts = [RNG.randint(0, cfg.vocab_size, (L,)).tolist() for L in (3, 8)]
    eng = servable.engine(max_slots=2, cache_len=32, collect_logits=True)
    handles = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run()
    for h, p in zip(handles, prompts):
        want_toks, want_logs = _sequential(servable, p, 4, 32)
        assert h.tokens == want_toks
        for got, want in zip(h.step_logits, want_logs):
            np.testing.assert_allclose(got, want, atol=1e-5)


def test_engine_matches_sequential_audio():
    cfg = get_config("whisper_base", smoke=True)
    params = init_model(jax.random.PRNGKey(3), cfg)
    servable = prepare_servable(params, cfg, ServingSpec(tile=(16, 16)))
    frames = [RNG.randn(cfg.n_audio_ctx, cfg.d_model).astype(np.float32)
              for _ in range(3)]
    prompts = [RNG.randint(0, cfg.vocab_size, (L,)).tolist()
               for L in (2, 6, 4)]
    eng = servable.engine(max_slots=3, cache_len=32, collect_logits=True)
    handles = [eng.submit(p, max_new_tokens=4, frames=f)
               for p, f in zip(prompts, frames)]
    eng.run()
    for h, p, f in zip(handles, prompts, frames):
        want_toks, want_logs = _sequential(servable, p, 4, 32,
                                           frames=jnp.asarray(f)[None])
        assert h.tokens == want_toks
        for got, want in zip(h.step_logits, want_logs):
            np.testing.assert_allclose(got, want, atol=1e-5)


# --------------------------------------------------------------------------
# slot lifecycle
# --------------------------------------------------------------------------

def test_slot_recycling_is_hygienic():
    """More requests than slots: recycled slots must serve their second
    request exactly like a fresh engine would (no state leak)."""
    cfg = get_config("recurrentgemma_9b", smoke=True)
    servable = _servable(cfg)
    prompts = [RNG.randint(0, cfg.vocab_size, (L,)).tolist()
               for L in (3, 9, 5, 12, 4, 7)]
    eng = servable.engine(max_slots=2, cache_len=64, collect_logits=True)
    handles = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run()
    assert all(h.done for h in handles)
    for h, p in zip(handles, prompts):
        want_toks, want_logs = _sequential(servable, p, 5, 64)
        assert h.tokens == want_toks
        for got, want in zip(h.step_logits, want_logs):
            np.testing.assert_allclose(got, want, atol=1e-5)
    assert eng.stats.completed == len(prompts)
    assert eng.stats.prefills == len(prompts)


def test_freed_slot_equals_fresh_cache():
    """free_slot zeroes attention KV AND recurrent state: slot state after
    free == slot state of a never-used cache."""
    cfg = get_config("recurrentgemma_9b", smoke=True)
    params = init_model(jax.random.PRNGKey(4), cfg)
    cache = model_api.init_cache(params, cfg, 3, 32)
    fresh = model_api.init_cache(params, cfg, 3, 32)
    tok = jnp.asarray(RNG.randint(0, cfg.vocab_size, (3, 1)))
    for t in range(4):
        _, cache, _ = model_api.decode_step(params, cache, cfg, tok,
                                            jnp.full((3,), t, jnp.int32))
    cache = model_api.free_slot(cache, cfg, 1)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        model_api.read_slot(cache, cfg, 1),
        model_api.read_slot(fresh, cfg, 1))


def test_engine_callbacks_and_eos():
    cfg = get_config("deepseek_7b", smoke=True)
    servable = _servable(cfg)
    seen = []
    done = []
    eng = servable.engine(max_slots=2, cache_len=64)
    h = eng.submit(RNG.randint(0, cfg.vocab_size, (4,)).tolist(),
                   max_new_tokens=8,
                   on_token=lambda rid, tok: seen.append((rid, tok)),
                   on_done=lambda rid, toks: done.append((rid, toks)))
    eng.run()
    assert [t for _, t in seen] == h.tokens
    assert done == [(h.req_id, h.tokens)]
    # eos stops early: replay with eos set to the first emitted token
    eng2 = servable.engine(max_slots=2, cache_len=64)
    h2 = eng2.submit(list(h.prompt), max_new_tokens=8, eos_id=h.tokens[0])
    eng2.run()
    assert h2.tokens == h.tokens[:1]


def test_engine_rejects_bad_requests():
    """Invalid requests are rejected AT SUBMISSION with a structured
    FailureReason (never a late prefill/decode crash); submit() does not
    raise for request-level problems."""
    cfg = get_config("deepseek_7b", smoke=True)
    servable = _servable(cfg)
    eng = servable.engine(max_slots=1, cache_len=16)
    h = eng.submit([], max_new_tokens=4)
    assert h.status == "failed" and not h.done
    assert h.failure.code == "rejected" and "empty" in h.failure.message
    h = eng.submit([1, 2, 3], max_new_tokens=16)    # overflows cache_len
    assert h.status == "failed"
    assert h.failure.code == "rejected"
    assert "cache_len" in h.failure.message
    h = eng.submit([1, 2, 3], max_new_tokens=0)
    assert h.failure.code == "rejected"
    assert eng.stats.rejected == 3 and eng.stats.failed == 3
    # rejected handles still drain through run() (queue conservation) and
    # a valid follow-up request is unaffected
    ok = eng.submit([1, 2, 3], max_new_tokens=4)
    done = eng.run()
    assert {r.req_id for r in done} == {0, 1, 2, ok.req_id}
    assert ok.done and len(ok.tokens) == 4
    bert = get_config("bert_base", smoke=True)
    bert_servable = prepare_servable(init_model(jax.random.PRNGKey(0), bert),
                                     bert, ServingSpec(tile=(16, 16)))
    with pytest.raises(ValueError):
        bert_servable.engine(max_slots=2)


def test_registry_thread_safety():
    """Concurrent admissions share one plan build per pattern (satellite:
    lock around PatternRegistry lookup/insert)."""
    import threading
    from repro.core.pattern_reuse import PatternRegistry

    reg = PatternRegistry()
    built = []

    def builder():
        built.append(1)
        return object()

    def worker():
        for _ in range(200):
            reg.cached("k", builder)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(built) == 1
    assert reg.stats.misses == 1
    assert reg.stats.hits == 8 * 200 - 1
