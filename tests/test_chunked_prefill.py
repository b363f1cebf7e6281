"""Chunked prefill parity: the SLO scheduler's sliced prompt admission
(engine ``sched=SchedSpec(max_chunk=...)``) must be BIT-EXACT against the
legacy one-shot admission for greedy decode on every decode-capable mixer
family, on dense AND paged KV.

Why parity holds: each chunk runs through ``models.api.prefill_suffix``
with positions ``start + arange(c)`` against the slot's already-resident
state -- dense rings attend-before-write over the concatenated (ring view
+ fresh chunk) K/V with exact-zero masked terms, MLA scatters latents then
expands, SSM/RG-LRU seed their inter-chunk scans with the slot's carried
state and real conv history. The einsum structure matches the one-shot
path, so greedy token streams are equal and logits agree to float32
tolerance (the PR 7 "numerically-equal-not-bitwise" contract).
"""
import jax
import numpy as np
import pytest

from repro.configs.base import LayerKind, ModelConfig
from repro.configs.registry import get_config
from repro.models import init_model
from repro.serving import SchedSpec, ServingSpec, prepare_servable

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

RNG = np.random.RandomState(7)

ATTN_TARGETS = ("attn/wq", "attn/wk", "attn/wv", "attn/wo")


def _attn_cfg():
    return ModelConfig(
        arch="chunk-attn-smoke", family="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
        pattern=(LayerKind("attn", "dense"),), dtype="float32")


def _mla_cfg():
    return ModelConfig(
        arch="chunk-mla-smoke", family="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=512,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        pattern=(LayerKind("mla", "dense"),), dtype="float32")


def _windowed_cfg():
    """Mixed local+global attention: the chunk path must respect the ring
    hazard (attend-before-write) on the windowed layers."""
    return ModelConfig(
        arch="chunk-window-smoke", family="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
        pattern=(LayerKind("attn", "dense", window=16),
                 LayerKind("attn", "dense")), dtype="float32")


CFGS = {
    "attn": _attn_cfg,
    "mla": _mla_cfg,
    "windowed": _windowed_cfg,
    # hybrid recurrent families: chunk continuation seeds the SSD scan /
    # RG-LRU recurrence with the slot's carried state + conv history
    "hybrid-ssm": lambda: get_config("mamba2_780m", smoke=True),
    "hybrid-rglru": lambda: get_config("recurrentgemma_9b", smoke=True),
    # MLA under YaRN with drop-free MoE layers: a token's routed experts do
    # not depend on the chunk it arrives in
    "moe-v2-lite": lambda: get_config("deepseek_v2_lite_16b", smoke=True),
}

SCHED = SchedSpec(max_chunk=8, token_budget=16)

# mixed lengths: shorter than one chunk, multi-chunk, chunk-boundary exact
PROMPTS_LENS = (5, 23, 3, 37)


def _servable(cfg, **kw):
    params = init_model(jax.random.PRNGKey(1), cfg)
    return prepare_servable(params, cfg, ServingSpec(
        tile=(16, 16), sparsity=0.5, prune="oneshot",
        targets=ATTN_TARGETS, **kw))


def _prompts(cfg, lens=PROMPTS_LENS):
    rng = np.random.RandomState(3)
    return [rng.randint(0, cfg.vocab_size, (n,)).tolist() for n in lens]


def _drain(sv, prompts, max_new=6, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("cache_len", 64)
    eng = sv.engine(max_queue=16, **kw)
    reqs = [eng.submit(list(p), max_new_tokens=max_new) for p in prompts]
    eng.run()
    return eng, reqs


def _assert_parity(base_reqs, sched_reqs, tag):
    for rb, rs in zip(base_reqs, sched_reqs):
        assert rb.status == rs.status == "done", (
            tag, rb.status, rs.status, rs.failure)
        assert rb.tokens == rs.tokens, (tag, rb.tokens, rs.tokens)


@pytest.mark.parametrize("family", sorted(CFGS))
def test_chunked_equals_oneshot_dense(family):
    cfg = CFGS[family]()
    sv = _servable(cfg)
    prompts = _prompts(cfg)
    _, base = _drain(sv, prompts)
    eng, chunked = _drain(sv, prompts, sched=SCHED)
    assert eng._chunking
    assert eng.stats.prefill_chunks > len(prompts)  # real multi-chunk work
    _assert_parity(base, chunked, family)
    eng.verify_invariants()


@pytest.mark.parametrize("family", ["attn", "mla", "moe-v2-lite"])
def test_chunked_equals_oneshot_paged(family):
    cfg = CFGS[family]()
    sv = _servable(cfg, kv_layout="paged", kv_page_size=8)
    prompts = _prompts(cfg)
    _, base = _drain(sv, prompts)
    eng, chunked = _drain(sv, prompts, sched=SCHED)
    assert eng.kv_layout == "paged" and eng._chunking
    _assert_parity(base, chunked, family + "+paged")
    assert eng.kv_stats()["peak_pages_used"] > 0
    eng.verify_invariants()


def test_chunk_boundary_on_window_edge():
    """Chunk boundaries landing exactly on the attention window edge (and
    on ring-wrap points) must not perturb the stream: prompt length ==
    k * window with max_chunk == window."""
    cfg = _windowed_cfg()                       # window = 16
    sv = _servable(cfg)
    prompts = _prompts(cfg, lens=(32, 16, 48))  # exact multiples of 16
    _, base = _drain(sv, prompts, max_new=8)
    eng, chunked = _drain(sv, prompts, max_new=8,
                          sched=SchedSpec(max_chunk=16, token_budget=16))
    _assert_parity(base, chunked, "window-edge")
    eng.verify_invariants()


def test_chunked_prefill_shares_prefix_pages():
    """The chunked admission path keeps the paged engine's prefix sharing:
    a completed request's full prompt pages publish at (chunked) prefill
    completion, and a later sharer serves its prefix from them -- matched
    at slot claim time, before any chunk runs. (Two requests admitted in
    the SAME window cannot share: publication happens at completion.)"""
    cfg = _attn_cfg()
    sv = _servable(cfg, kv_layout="paged", kv_page_size=8)
    shared = list(range(1, 33))
    prompts = [shared + [100, 101, 102], shared + [200, 201]]
    base_eng = sv.engine(max_slots=4, cache_len=64, max_queue=16)
    base = [base_eng.submit(p, max_new_tokens=6) for p in prompts]
    base_eng.run()
    eng = sv.engine(max_slots=4, cache_len=64, max_queue=16, sched=SCHED)
    first = eng.submit(prompts[0], max_new_tokens=6)
    eng.run()                                   # publish prompt pages
    second = eng.submit(prompts[1], max_new_tokens=6)
    eng.run()                                   # prefix hit via pages
    _assert_parity(base, [first, second], "prefix+chunk")
    assert eng.stats.prefix_hit_tokens >= 32
    assert eng.stats.prefilled_tokens < sum(len(p) for p in prompts)
    eng.verify_invariants()


def test_preempt_resume_of_half_prefilled_request():
    """A request preempted MID-PREFILL (slot held, pos still -1) restarts
    its prefill from scratch on re-admission and finishes with the exact
    greedy stream -- and never retains pages (retention requires generated
    tokens)."""
    cfg = _attn_cfg()
    sv = _servable(cfg)
    prompt = _prompts(cfg, lens=(40,))[0]
    # budget 4/window: the long prompt needs many windows to prefill
    eng = sv.engine(max_slots=1, cache_len=64, max_queue=16,
                    sched=SchedSpec(max_chunk=4, token_budget=4))
    a = eng.submit(prompt, max_new_tokens=6)
    for _ in range(3):
        eng.step()
    assert a.status == "active" and 0 < a.prefill_pos < a.prefill_target
    assert eng._pos[a.slot] == -1               # admitted but not decoding
    b = eng.submit([9, 8, 7], max_new_tokens=4, priority=10)
    eng.run()
    assert a.status == "done" and b.status == "done"
    assert a.n_preempted >= 1
    # oracle: the same request one-shot
    _, base = _drain(sv, [prompt])
    assert a.tokens == base[0].tokens
    eng.verify_invariants()


def test_budget_prevents_head_of_line_blocking():
    """With a token budget, a short prompt submitted behind a long one
    starts decoding before the long prefill completes (no HOL blocking);
    the legacy scheduler prefills the long prompt monolithically first."""
    cfg = _attn_cfg()
    sv = _servable(cfg)
    long_p = _prompts(cfg, lens=(48,))[0]
    eng = sv.engine(max_slots=2, cache_len=64, max_queue=16, sync_every=2,
                    sched=SchedSpec(max_chunk=8, token_budget=8,
                                    decode_priority=True))
    first_done_order = []
    a = eng.submit(long_p, max_new_tokens=4,
                   on_done=lambda rid, t: first_done_order.append("long"))
    b = eng.submit([5, 6, 7], max_new_tokens=4,
                   on_done=lambda rid, t: first_done_order.append("short"))
    eng.run()
    assert a.status == b.status == "done"
    assert first_done_order[0] == "short"
    # the short request got tokens while the long prefill was in flight
    assert b.first_token_at < a.first_token_at
    eng.verify_invariants()


def test_chunking_gate_falls_back_for_moe():
    """The MoE layer drops nothing, so no MoE config falls back to one-shot
    admission any more: it chunks, and its tokens equal one-shot's."""
    cfg = get_config("qwen3_moe_235b_a22b", smoke=True)
    sv = _servable(cfg)
    prompts = _prompts(cfg)
    _, base = _drain(sv, prompts)
    eng, chunked = _drain(sv, prompts, sched=SCHED)
    assert eng._chunking and eng.stats.prefill_chunks > len(prompts)
    _assert_parity(base, chunked, "moe")
    eng.verify_invariants()


def test_sched_spec_roundtrip_via_serving_spec():
    spec = ServingSpec(tile=(16, 16), sparsity=0.5, prune="oneshot",
                       targets=ATTN_TARGETS,
                       sched=SchedSpec(max_chunk=32, token_budget=64,
                                       fast_fail=True))
    back = ServingSpec.from_dict(spec.to_dict())
    assert back.sched == spec.sched
    cfg = _attn_cfg()
    params = init_model(jax.random.PRNGKey(1), cfg)
    sv = prepare_servable(params, cfg, spec)
    eng = sv.engine(max_slots=2, cache_len=64)
    assert eng.sched == spec.sched and eng._chunking    # spec-level default
    eng2 = sv.engine(max_slots=2, cache_len=64,
                     sched=SchedSpec(max_chunk=0))
    assert not eng2._chunking                           # kwarg overrides


@needs8
def test_chunked_parity_tp8():
    """Chunked prefill through the mesh suffix jit (out_shardings pinned)
    matches the unsharded stream bit-exactly."""
    cfg = ModelConfig(
        arch="chunk-tp-smoke", family="dense", n_layers=2, d_model=256,
        n_heads=8, n_kv_heads=8, head_dim=32, d_ff=512, vocab_size=512,
        pattern=(LayerKind("attn", "dense"),), dtype="float32")
    params = init_model(jax.random.PRNGKey(3), cfg)
    mk = lambda **kw: prepare_servable(params, cfg, ServingSpec(
        tile=(32, 32), sparsity=0.5, prune="oneshot",
        targets=ATTN_TARGETS, **kw))
    ref = mk()
    tp = mk(mesh_shape=(1, 8), partition="tp")
    prompts = _prompts(cfg, lens=(23, 5))
    _, base = _drain(ref, prompts, max_slots=2)
    eng, chunked = _drain(tp, prompts, max_slots=2, sched=SCHED)
    assert eng._chunking and eng.mesh is not None
    _assert_parity(base, chunked, "tp8")
    eng.verify_invariants()
