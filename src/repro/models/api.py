"""Family-dispatching model API used by the launcher, tests and benchmarks.

batch keys by family:
  lm-like ('dense','moe','ssm','hybrid'): tokens (B,S) [, labels]
  'vlm':   tokens + mm_embeds (B,P,d)
  'audio': frames (B,T_audio,d) + tokens (B,S)
  'bert':  tokens
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import bert as bert_mod
from repro.models import encdec as encdec_mod
from repro.models import lm as lm_mod
from repro.models.attention import as_slot_positions
from repro.models.sampling import sample_tokens


def init_model(key, cfg: ModelConfig):
    if cfg.family == "bert":
        return bert_mod.init_bert(key, cfg)
    if cfg.family == "audio":
        return encdec_mod.init_encdec(key, cfg)
    return lm_mod.init_lm(key, cfg)


def model_forward(params, cfg: ModelConfig, batch, packs=None):
    """-> (logits f32, aux)."""
    if cfg.family == "bert":
        return bert_mod.forward(params, cfg, batch["tokens"], packs=packs), \
            jnp.zeros((), jnp.float32)
    if cfg.family == "audio":
        return encdec_mod.forward(params, cfg, batch["frames"], batch["tokens"])
    if cfg.family == "vlm":
        return lm_mod.forward(params, cfg, batch["tokens"],
                              mm_embeds=batch.get("mm_embeds"), packs=packs)
    return lm_mod.forward(params, cfg, batch["tokens"], packs=packs)


def init_cache(params, cfg: ModelConfig, batch_size, cache_len, frames=None,
               paged=None):
    """``paged`` (models.common.PagedLayout or None): page-pool storage for
    linear attention/MLA KV (lm-family only; serving/paging.py owns the
    allocator that hands out page ids)."""
    if cfg.family == "audio":
        if paged is not None:
            raise ValueError("paged KV is not supported for family 'audio'")
        return encdec_mod.init_cache(params, cfg, frames, cache_len)
    if cfg.family == "bert":
        raise ValueError("encoder-only arch has no decode step")
    return lm_mod.init_cache(cfg, batch_size, cache_len, paged=paged)


def cache_shardings(cache, mesh):
    """NamedSharding tree for a decode cache on ``mesh``: request slots
    (the batch dim) shard over "data", KV heads / recurrent state over
    "model" -- the divisibility-aware rules of
    ``launch/sharding.spec_for_cache``. ``cache`` may be real arrays or
    ShapeDtypeStructs."""
    from repro.launch.sharding import cache_shardings as _cache_shardings
    return _cache_shardings(cache, mesh)


def shard_cache(cache, cfg: ModelConfig, mesh):
    """Place a decode cache on ``mesh`` (see :func:`cache_shardings`).
    Slot lifecycle ops (:func:`write_slot` / :func:`free_slot` /
    :func:`reset_slot`) are sharding-preserving device scatters, so a
    placed cache never gathers back to host across its lifetime."""
    return jax.device_put(cache, cache_shardings(cache, mesh))


def decode_step(params, cache, cfg: ModelConfig, token, pos, packs=None):
    """-> (logits, cache, MoE rows: (n_held,) int32 assignments each held
    expert computed; zero length without MoE). ``pos``: scalar
    (single-request convention, broadcast) or int32 (B,) ragged per-slot
    positions; rows with pos < 0 are inactive slots whose cache state is
    left untouched (continuous batching, docs/API.md)."""
    if cfg.family == "audio":
        logits, cache = encdec_mod.decode_step(params, cache, cfg, token, pos)
        return logits, cache, jnp.zeros((cfg.n_held,), jnp.int32)
    if cfg.family == "bert":
        raise ValueError("encoder-only arch has no decode step")
    return lm_mod.decode_step(params, cache, cfg, token, pos, packs=packs)


def decode_many(params, cache, cfg: ModelConfig, token, pos, n_steps, *,
                packs=None, remaining=None, eos_id=None, key=None,
                temperature: float = 0.0, top_k: int = 0):
    """Fused multi-token decode: ``n_steps`` decode steps inside ONE
    ``lax.scan``, with sampling, per-slot EOS/stop handling and position
    bookkeeping all on device -- the host only syncs once per window
    (repro/serving/engine.py drains the emitted tokens at sync points).

    Args:
      token: (B, 1) int32 -- the current token of each request slot.
      pos: scalar or ragged (B,) int32 slot positions (the ``decode_step``
        convention; pos < 0 = inactive slot, a device-side no-op).
      n_steps: static window length K.
      remaining: optional (B,) int32 token budget per slot; a slot that
        exhausts it mid-window deactivates itself (pos -> -1) and emits
        nothing further. None = unbounded within the window.
      eos_id: optional scalar or (B,) int32 stop token per slot (-1 =
        none); sampling it deactivates the slot *after* emitting it.
      key / temperature / top_k: sampling config (models/sampling.py);
        temperature 0 = greedy, and the PRNG key is folded by (slot,
        position) so fused and per-step decoding sample identically.

    Non-finite guard: a slot whose logits go non-finite (NaN/inf anywhere
    in its row) is quarantined ON DEVICE mid-window -- the poisoned token
    is never emitted (``valid`` False), the slot deactivates (pos -> -1)
    and rides the rest of the window as a no-op, and ``state['failed']``
    flags it at the sync point. Other slots are untouched: per-slot
    compute is batch-row independent, so they finish bit-identically to a
    window with no poisoned co-resident (tests/test_chaos.py).

    Returns ``(tokens (K, B) int32, valid (K, B) bool, state)`` where
    ``valid[k, b]`` marks tokens actually emitted by live slots and
    ``state`` is the carry to continue from:
    ``{'token', 'pos', 'remaining', 'failed', 'cache'}``, with
    ``'moe_rows'``, the window's MoE rows (:func:`decode_step`) summed
    over its steps.
    """
    b = token.shape[0]
    pos = as_slot_positions(pos, b)
    if remaining is None:
        remaining = jnp.full((b,), jnp.iinfo(jnp.int32).max // 2, jnp.int32)
    else:
        remaining = jnp.asarray(remaining, jnp.int32)
    if eos_id is None:
        eos = jnp.full((b,), -1, jnp.int32)
    else:
        eos = jnp.broadcast_to(jnp.asarray(eos_id, jnp.int32), (b,))
    if key is None:
        key = jax.random.PRNGKey(0)

    failed0 = jnp.zeros((b,), bool)

    def body(carry, _):
        tok, p, rem, bad, c, moe_rows = carry
        logits, c, r = decode_step(params, c, cfg, tok, p, packs=packs)
        rows = logits[:, 0, :]
        finite = jnp.isfinite(rows).all(axis=-1)
        nxt = sample_tokens(rows, key, p, temperature=temperature,
                            top_k=top_k)
        active = p >= 0
        poisoned = active & ~finite
        emit = active & finite
        nxt = jnp.where(emit, nxt, 0)
        rem = jnp.where(emit, rem - 1, rem)
        done = emit & ((rem <= 0) | ((eos >= 0) & (nxt == eos)))
        new_pos = jnp.where(done | poisoned, -1,
                            jnp.where(active, p + 1, p))
        new_tok = jnp.where(emit, nxt, tok[:, 0])[:, None]
        return (new_tok, new_pos, rem, bad | poisoned, c, moe_rows + r), \
            (nxt, emit)

    (token, pos, remaining, failed, cache, moe_rows), (toks, valid) = \
        jax.lax.scan(body, (token, pos, remaining, failed0, cache,
                            jnp.zeros((cfg.n_held,), jnp.int32)),
                     None, length=n_steps)
    state = {"token": token, "pos": pos, "remaining": remaining,
             "failed": failed, "cache": cache, "moe_rows": moe_rows}
    return toks, valid, state


def prefill_cache(params, cache, cfg: ModelConfig, tokens, length=None,
                  packs=None):
    """One-pass prompt prefill into a decode cache (lm-family layouts):
    forward-path compute for tokens (B, S), bulk cache writes for positions
    0..length-1 (length <= S; the tail is bucket padding). Returns
    (logits (B, S, V), cache, MoE rows as :func:`decode_step`'s). Audio
    prefills through the scanned decode
    path instead (its decoder prompts are BOS-sized)."""
    if cfg.family in ("audio", "bert"):
        raise ValueError(f"no one-pass prefill for family {cfg.family!r}")
    return lm_mod.prefill_cache(params, cache, cfg, tokens, length,
                                packs=packs)


# ---------------------------------------------------------------------------
# slot lifecycle: the batch dimension of a decode cache is request slots
# (continuous batching, repro/serving/engine.py)
# ---------------------------------------------------------------------------

def _slot_mod(cfg: ModelConfig):
    if cfg.family == "bert":
        raise ValueError("encoder-only arch has no decode cache")
    return encdec_mod if cfg.family == "audio" else lm_mod


def reset_slot(cache, cfg: ModelConfig, slot):
    """Zero one request slot: attention KV (pos_map -> empty) and SSM/RgLRU
    recurrent + conv state, so a recycled slot cannot leak its previous
    request. Returns the updated cache."""
    return _slot_mod(cfg).reset_slot(cache, slot)


def alloc_slot(cache, cfg: ModelConfig, slot):
    """Claim ``slot`` for a new request: identical state-wise to
    :func:`reset_slot` (a fresh slot IS a zeroed slot); named separately so
    admission and retirement read as a lifecycle."""
    return _slot_mod(cfg).reset_slot(cache, slot)


def free_slot(cache, cfg: ModelConfig, slot):
    """Retire ``slot`` after request completion (state hygiene: the zeroing
    is what guarantees recycled slots start from a fresh cache)."""
    return _slot_mod(cfg).reset_slot(cache, slot)


def write_slot(cache, cfg: ModelConfig, slot, sub):
    """Insert a batch-1 cache (e.g. a prefill result) into ``slot``."""
    return _slot_mod(cfg).write_slot(cache, slot, sub)


def read_slot(cache, cfg: ModelConfig, slot):
    """Extract ``slot`` as a batch-1 cache (write_slot's inverse)."""
    return _slot_mod(cfg).read_slot(cache, slot)


def write_slot_paged(cache, cfg: ModelConfig, slot, sub, page_row):
    """Insert a dense batch-1 prefill result into paged slot ``slot``,
    scattering KV rows into the physical pages named by ``page_row``
    (lm-family paged caches only; see lm.write_slot_paged)."""
    if cfg.family in ("audio", "bert"):
        raise ValueError(f"no paged slots for family {cfg.family!r}")
    return lm_mod.write_slot_paged(cache, slot, sub, page_row)


def restore_slot_paged(cache, cfg: ModelConfig, slot, page_row, resume_len):
    """Re-attach retained pages to ``slot`` after preemption (bit-exact,
    zero prefill; see lm.restore_slot_paged)."""
    if cfg.family in ("audio", "bert"):
        raise ValueError(f"no paged slots for family {cfg.family!r}")
    return lm_mod.restore_slot_paged(cache, slot, page_row, resume_len)


def prefill_suffix(params, cache, cfg: ModelConfig, tokens, slot, start,
                   length=None, packs=None):
    """Prefill only the suffix of a prompt whose first ``start`` tokens are
    already resident in slot ``slot`` of the batched engine cache: the
    paged shared-prefix path (prefix-cache hit) and the dense-KV chunked-
    prefill path share this entry point (see lm.prefill_suffix). Returns
    :func:`prefill_cache`'s three values."""
    if cfg.family in ("audio", "bert"):
        raise ValueError(f"no one-pass prefill for family {cfg.family!r}")
    return lm_mod.prefill_suffix(params, cache, cfg, tokens, slot, start,
                                 length, packs=packs)
