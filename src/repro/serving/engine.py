"""Continuous-batching serving engine: request slots over one batched decode.

The model layer's decode path takes a ragged ``pos: (B,)`` vector (one
absolute position per batch row, -1 = inactive; models/api.py), which turns
the batch dimension into *request slots*. This module adds the request-level
machinery on top:

  * an **admission queue** -- ``submit()`` enqueues requests; each ``step()``
    admits as many as there are free slots (highest ``priority`` first, FIFO
    within a priority class). The queue is optionally bounded
    (``max_queue``) with a pluggable backpressure policy (``overflow`` =
    ``'reject'`` / ``'shed-oldest'`` / ``'block'``, spec.OVERFLOW_POLICIES);
  * **prefill-into-cache** -- an admitted prompt runs ONE forward pass on a
    batch-1 cache (``models.api.prefill_cache``; audio scans the decode
    path instead). Prompt lengths are padded to power-of-two *buckets* so
    the per-bucket jit executables stay warm, and the result is inserted
    into the engine cache with ``write_slot``;
  * **fused decode windows** -- with ``sync_every = K > 1`` each ``step()``
    runs up to K decode steps inside ONE jitted ``lax.scan``
    (``models.api.decode_many``); the host syncs once per window to drain
    emitted tokens, fire callbacks, recycle finished slots and admit
    queued requests. ``sync_every=1`` (or ``collect_logits=True``) keeps
    the one-decode-per-step loop;
  * **SLO-aware scheduling** (``sched=SchedSpec(...)``, docs/API.md §SLO
    scheduling) -- chunked prefill splits long prompts into
    ``max_chunk``-sized slices run through the masked suffix-prefill path
    between decode windows (a partially-prefilled request holds its slot
    as a pos -1 no-op row; chunked == one-shot bit-exact), a per-window
    ``token_budget`` with ``decode_priority`` reserve eliminates
    head-of-line blocking, and graceful overload degradation fast-fails
    un-meetable deadlines at admission and sheds the newest low-priority
    queued traffic once the estimated queue delay exceeds
    ``max_queue_delay_s`` (both from MEASURED prefill/decode rates);
  * **request lifecycle robustness** (docs/API.md §Engine robustness) --
    every submitted request ends in EXACTLY ONE terminal status (``done``
    / ``failed`` / ``cancelled`` / ``shed``), with a structured
    :class:`FailureReason` on the non-success paths:
      - **deadlines** (``submit(deadline_s=...)``) and **cancellation**
        (:meth:`ServingEngine.cancel`) are enforced at window-sync points,
        so the fused decode stays one jitted scan between checks;
      - **preemption**: under slot pressure a queued request of strictly
        higher priority evicts the lowest-priority in-flight request --
        the victim's slot is freed with the usual recycle hygiene and the
        victim requeued; on re-admission it resumes via ``prefill_cache``
        over prompt + already-generated tokens (greedy streams continue
        exactly; sampled streams may re-key if the slot changed);
      - **non-finite quarantine**: decode logits are finite-checked on
        device (per-step and inside the fused scan,
        ``models.api.decode_many``); a poisoned slot fails with a
        structured reason while co-resident slots finish bit-identically
        to an uninjected run;
      - **failure isolation**: admission errors fail only their request
        (slot restored -- the try/except hygiene paths); a decode-window
        error fails the active requests, rebuilds the (donated, possibly
        invalidated) cache, and leaves the engine serving;
      - an optional **watchdog** (``watchdog_timeout_s``) detects stuck
        windows/syncs from a background thread (detection-only: a hung
        XLA dispatch cannot be cancelled, but it can be seen);
      - **chaos hooks** (``chaos=repro.runtime.chaos.ChaosInjector()``)
        fire at alloc/prefill/window/sync so the fault paths above are
        testable deterministically (tests/test_chaos.py).
  * **slot lifecycle** -- any retirement (completion, failure, cancel,
    preemption) ``free_slot``-zeroes the slot (attention KV *and*
    SSM/RgLRU recurrent state), so a recycled slot cannot leak its
    previous request.

Construct via :meth:`repro.serving.Servable.engine`::

    engine = servable.engine(max_slots=16, cache_len=512, sync_every=8,
                             max_queue=64, overflow="reject")
    h = engine.submit([1, 2, 3], max_new_tokens=32, priority=1,
                      deadline_s=30.0)
    engine.run()                      # drain queue + active slots
    print(h.status, h.tokens)         # 'done' + greedy continuation

Sampling is configured per engine (``temperature`` / ``top_k`` / ``seed``);
the PRNG key is folded by (slot, position), so fused and per-step decoding
emit identical tokens for the same seed (models/sampling.py).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import logging
import os
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import api as model_api
from repro.models.common import PagedLayout
from repro.models.sampling import sample_token_row
from repro.runtime import chaos as chaos_mod
from repro.runtime.trace import timed
from repro.serving.paging import PagePool, PagePoolExhausted, pages_needed
from repro.serving.prefix_cache import PrefixCache
from repro.serving.spec import KV_LAYOUTS, OVERFLOW_POLICIES, SchedSpec

__all__ = ["EngineRequest", "EngineStats", "FailureReason", "ServingEngine",
           "TERMINAL_STATES"]

log = logging.getLogger("repro.serving")

#: the exactly-once terminal accounting: every submit() ends in ONE of these
TERMINAL_STATES = frozenset({"done", "failed", "cancelled", "shed"})


@dataclasses.dataclass(frozen=True)
class FailureReason:
    """Structured reason attached to every non-success terminal request.

    ``code`` is one of the class constants below (the machine-readable
    taxonomy, stable across releases); ``message`` carries the
    human-readable detail (offending sizes, exception text, ...).
    """

    code: str
    message: str = ""

    REJECTED = "rejected"                # invalid at submission
    QUEUE_FULL = "queue_full"            # shed by backpressure policy
    OVERLOAD = "overload_shed"           # SLO shedding (SchedSpec knobs)
    DEADLINE = "deadline"                # deadline_s expired (sync point)
    CANCELLED = "cancelled"              # engine.cancel(handle)
    PREFILL_ERROR = "prefill_error"      # admission/prefill raised
    NONFINITE_LOGITS = "nonfinite_logits"  # NaN/inf quarantine
    ENGINE_ERROR = "engine_error"        # decode window raised
    KV_PAGES = "kv_pages_exhausted"      # page pool dry with no way to drain

    def __str__(self):
        return f"{self.code}: {self.message}" if self.message else self.code


@dataclasses.dataclass
class EngineRequest:
    """One submitted request; doubles as the caller's result handle."""

    req_id: int
    prompt: np.ndarray                      # (L,) int32
    max_new_tokens: int
    eos_id: Optional[int] = None
    frames: Optional[np.ndarray] = None     # audio family: encoder input
    on_token: Optional[Callable[[int, int], None]] = None
    on_done: Optional[Callable[[int, List[int]], None]] = None
    priority: int = 0                       # higher preempts lower
    deadline_at: Optional[float] = None     # absolute time.monotonic()

    # engine-owned state
    status: str = "queued"          # queued|active|done|failed|cancelled|shed
    failure: Optional[FailureReason] = None
    slot: int = -1
    pos: int = -1                           # next decode position
    tokens: List[int] = dataclasses.field(default_factory=list)
    step_logits: List[np.ndarray] = dataclasses.field(default_factory=list)
    done: bool = False                      # status == 'done' (back-compat)
    cancel_requested: bool = False
    n_preempted: int = 0
    admit_seq: int = -1                     # monotonic admission counter
    # chunked prefill (docs/API.md §SLO scheduling): prompt tokens already
    # resident in the slot vs the full prefill length; pos == target (or
    # target == 0) = the request is decodable
    prefill_pos: int = 0
    prefill_target: int = 0
    # SLO timestamps (time.monotonic): submission, first emitted token and
    # terminal transition -- the open-loop bench derives TTFT and
    # per-token latency from these (benchmarks/serving_bench.py)
    submitted_at: float = 0.0
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None

    @property
    def n_generated(self) -> int:
        return len(self.tokens)

    @property
    def finished(self) -> bool:
        """True once the request reached ANY terminal state (``done`` stays
        success-only)."""
        return self.status in TERMINAL_STATES


@dataclasses.dataclass
class EngineStats:
    steps: int = 0                  # decode steps (fused windows count K)
    windows: int = 0                # device dispatches (fused or per-step)
    prefills: int = 0
    tokens_generated: int = 0
    occupancy_sum: int = 0          # sum over steps of active slots
    completed: int = 0
    # lifecycle accounting (completed + failed + cancelled + shed covers
    # every request that ever reached a terminal state)
    failed: int = 0
    cancelled: int = 0
    shed: int = 0
    rejected: int = 0               # failed at submission (subset of failed)
    preemptions: int = 0
    deadline_misses: int = 0        # subset of failed/cancelled-by-deadline
    watchdog_stalls: int = 0
    # paged-KV accounting (dense engines fill prefilled_tokens only):
    # tokens actually run through a prefill forward (full or suffix),
    # prompt tokens served from shared prefix pages instead, and
    # preemption resumes that re-attached retained pages with NO prefill
    prefilled_tokens: int = 0
    prefix_hit_tokens: int = 0
    page_resumes: int = 0
    prefill_chunks: int = 0         # chunk dispatches (SLO scheduler)
    bucket_hits: Dict[int, int] = dataclasses.field(
        default_factory=lambda: collections.defaultdict(int))
    # wall-clock breakdown of the serving loop (seconds): prompt prefill
    # (compute + slot insert), decode windows (device call until outputs
    # materialize on host), and host-side sync work (token drain,
    # callbacks, slot recycling) -- the summed durations of the
    # repro.engine.admit / .window / .sync spans (runtime/trace.py)
    prefill_s: float = 0.0
    decode_s: float = 0.0
    sync_s: float = 0.0
    # MoE layers: assignments the held experts computed, and held experts x
    # MoE layers x forwards (decode steps, prefills, prefill chunks) that
    # could have taken them -- set also on the admit and window spans
    moe_rows: int = 0
    moe_expert_slots: int = 0

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / self.steps if self.steps else 0.0

    def as_dict(self) -> Dict:
        return {"steps": self.steps, "windows": self.windows,
                "prefills": self.prefills,
                "tokens_generated": self.tokens_generated,
                "completed": self.completed,
                "failed": self.failed, "cancelled": self.cancelled,
                "shed": self.shed, "rejected": self.rejected,
                "preemptions": self.preemptions,
                "deadline_misses": self.deadline_misses,
                "watchdog_stalls": self.watchdog_stalls,
                "prefilled_tokens": self.prefilled_tokens,
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "page_resumes": self.page_resumes,
                "prefill_chunks": self.prefill_chunks,
                "mean_occupancy": round(self.mean_occupancy, 3),
                "prefill_buckets": dict(self.bucket_hits),
                "prefill_s": round(self.prefill_s, 4),
                "decode_s": round(self.decode_s, 4),
                "sync_s": round(self.sync_s, 4),
                "moe_rows": self.moe_rows,
                "moe_expert_slots": self.moe_expert_slots}


class ServingEngine:
    """Slot-addressable continuous-batching engine over a Servable.

    ``max_slots`` bounds request concurrency (the static batch of the one
    jitted decode executable); ``cache_len`` bounds prompt + generation
    length per slot. ``sync_every = K`` fuses up to K decode steps into one
    on-device window between host syncs (``collect_logits`` forces K = 1).

    Robustness knobs (docs/API.md §Engine robustness): ``max_queue`` +
    ``overflow`` bound the admission queue (policies in
    ``spec.OVERFLOW_POLICIES``); ``watchdog_timeout_s`` arms a stuck-window
    detector (``on_stall(label, elapsed)`` optional callback; stalls also
    snapshot into ``stats_dict()['watchdog']``); ``chaos`` attaches a
    :class:`repro.runtime.chaos.ChaosInjector` whose
    alloc/prefill/window/sync/arrival/chunk sites this engine fires;
    ``sched`` (:class:`repro.serving.SchedSpec`; kwarg > spec.sched)
    enables the SLO scheduler -- chunked prefill, per-window token
    budget, deadline fast-fail and overload shedding (module docstring).
    """

    def __init__(self, servable, max_slots: int = 8, cache_len: int = 256,
                 *, min_bucket: int = 8, collect_logits: bool = False,
                 sync_every: int = 8, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0,
                 max_queue: Optional[int] = None, overflow: str = "reject",
                 watchdog_timeout_s: Optional[float] = None,
                 on_stall: Optional[Callable[[str, float], None]] = None,
                 chaos: Optional["chaos_mod.ChaosInjector"] = None,
                 kv_layout: Optional[str] = None,
                 kv_pool_pages: Optional[int] = None,
                 sched: Optional[SchedSpec] = None):
        if servable.cfg.family == "bert":
            raise ValueError("encoder-only arch has no decode step")
        if overflow not in OVERFLOW_POLICIES:
            raise ValueError(
                f"overflow={overflow!r} not in {OVERFLOW_POLICIES}")
        if max_queue is not None and int(max_queue) < 1:
            raise ValueError("max_queue must be >= 1 (or None = unbounded)")
        self.servable = servable
        self.cfg = servable.cfg
        self.max_slots = int(max_slots)
        self.cache_len = int(cache_len)
        # floor of 2: a length-1 "prefill" would hit the single-token decode
        # path (s == 1), which expects a pos argument
        self.min_bucket = max(2, int(min_bucket))
        self.collect_logits = collect_logits
        self.sync_every = 1 if collect_logits else max(1, int(sync_every))
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self._key = jax.random.PRNGKey(int(seed))
        self.stats = EngineStats()
        self.mesh = servable.mesh               # None = single-device path
        self.max_queue = None if max_queue is None else int(max_queue)
        self.overflow = overflow
        self._chaos = chaos
        self._watchdog = None
        self._user_on_stall = on_stall
        self._watchdog_snapshot: Optional[Dict] = None
        if watchdog_timeout_s is not None:
            # the engine interposes on the stall callback to snapshot its
            # queue/active/chunk state for stats_dict()['watchdog'];
            # detection semantics are the Watchdog's, unchanged
            self._watchdog = chaos_mod.Watchdog(watchdog_timeout_s,
                                                on_stall=self._on_stall)

        self._sub_template = None
        if self.cfg.family != "audio":
            # single-request cache template reused by every prefill (the
            # prefill is functional; audio rebuilds per request from frames)
            self._sub_template = model_api.init_cache(
                servable.params, self.cfg, 1, self.cache_len)
            if self.mesh is not None:
                from repro.launch.sharding import replicated
                self._sub_template = jax.device_put(
                    self._sub_template, replicated(self.mesh))

        # -- KV layout resolution: kwarg > REPRO_KV_LAYOUT env > spec ------
        layout = kv_layout or os.environ.get("REPRO_KV_LAYOUT") \
            or servable.spec.kv_layout
        if layout not in KV_LAYOUTS:
            raise ValueError(f"kv_layout={layout!r} not in {KV_LAYOUTS}")
        prefix_k, pattern_k, n_per, suffix_k = self.cfg.layer_plan()
        kinds = list(prefix_k) + (list(pattern_k) if n_per > 0 else []) \
            + list(suffix_k)
        pageable = [k for k in kinds
                    if k.mixer in ("attn", "mla") and k.window == 0]
        n_moe = sum(k.ffn == "moe" for k in prefix_k + suffix_k) + \
            n_per * sum(k.ffn == "moe" for k in pattern_k)
        #: held experts x MoE layers: the expert slots of one forward
        self._moe_slots = self.cfg.n_held * n_moe
        if layout == "paged":
            blocker = None
            if self.cfg.family == "audio":
                blocker = "family 'audio' (cross-attn caches are per-request)"
            elif self.cfg.kv_cache_quant:
                blocker = "kv_cache_quant (int8 page pools are future work)"
            elif servable.spec.data_shards > 1:
                blocker = "data-parallel mesh (page ids are a shared space)"
            elif not pageable:
                blocker = "no linear attention/MLA layers to page"
            if blocker is not None:
                log.info("kv_layout='paged' unavailable for this config "
                         "(%s); serving dense", blocker)
                layout = "dense"
        self.kv_layout = layout
        self._pool = None
        self._prefix_cache = None
        self._slot_pages: Dict[int, List[int]] = {}
        self._saved_pages: Dict[int, tuple] = {}
        self._layout = None
        if layout == "paged":
            # largest page size <= spec.kv_page_size dividing cache_len (the
            # table must tile the cache exactly); default pool capacity
            # matches the dense worst case so parity runs are apples to
            # apples -- kv_pool_pages shrinks it to create real pressure
            ps = min(int(servable.spec.kv_page_size), self.cache_len)
            while self.cache_len % ps:
                ps -= 1
            self.kv_page_size = ps
            self._table_width = self.cache_len // ps
            n_pages = int(kv_pool_pages) if kv_pool_pages is not None \
                else self.max_slots * self._table_width
            self._layout = PagedLayout(page_size=ps, n_pages=n_pages)
            self._pool = PagePool(n_pages, ps)
            self._prefix_cache = PrefixCache(self._pool, ps)
            # prefix sharing needs the masked suffix-prefill path (pure
            # global attention); preempt-resume page retention additionally
            # admits MLA (restore is layout-only, no recompute)
            self._can_share = all(k.mixer == "attn" and k.window == 0
                                  for k in kinds)
            self._can_retain = all(k.mixer in ("attn", "mla")
                                   and k.window == 0 for k in kinds)

        # -- SLO scheduling: kwarg > spec (docs/API.md §SLO scheduling) ---
        # sched arms deadline fast-fail and overload shedding regardless;
        # chunked prefill (max_chunk > 0) additionally needs the masked
        # chunk path every layer supports -- ineligible configs fall back
        # to one-shot admission with the other knobs still live
        self.sched = sched if sched is not None else servable.spec.sched
        self._chunking = False
        if self.sched is not None and self.sched.max_chunk > 0:
            blocker = None
            if self.cfg.family == "audio":
                blocker = "family 'audio' prefills through the decode path"
            elif self.cfg.kv_cache_quant:
                blocker = "kv_cache_quant (int8 KV has no masked chunk path)"
            if blocker is not None:
                log.info("chunked prefill unavailable for this config "
                         "(%s); scheduling runs without it", blocker)
            else:
                self._chunking = True
        # chunk lengths are QUANTIZED: every dispatched chunk is exactly
        # _chunk_len tokens or the prompt tail, never a budget-truncated
        # remainder -- each novel chunk length is a fresh suffix-jit shape
        # (an on-clock compile), so max_chunk is clamped to the window
        # budget and a chunk that no longer fits waits for the next window
        self._chunk_len = 0
        if self._chunking:
            self._chunk_len = self.sched.max_chunk
            if self.sched.token_budget > 0:
                self._chunk_len = min(self._chunk_len,
                                      self.sched.token_budget)
        #: req_ids whose fresh full-prompt pages publish to the prefix
        #: cache once their (chunked) prefill completes
        self._pending_publish: set = set()
        #: req_id -> (MoE rows on the device, chunks) of prefill passes
        #: that ended mid-prompt, read with the pass that ends it
        self._moe_carry: Dict[int, tuple] = {}

        self.cache = self._build_cache()
        # host-side byte accounting from the real device leaves
        self._kv_bytes_total = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(self.cache))
        if self._pool is not None:
            pool_bytes = 0
            def _acc(path, x):
                nonlocal pool_bytes
                name = getattr(path[-1], "key", None)
                if isinstance(name, str) and name.endswith("_pages"):
                    pool_bytes += x.nbytes
                return x
            jax.tree_util.tree_map_with_path(_acc, self.cache)
            self._pool.bytes_per_page = pool_bytes // self._pool.n_pages

        self._tokens = np.zeros((self.max_slots, 1), np.int32)
        self._pos = np.full((self.max_slots,), -1, np.int32)
        self._remaining = np.zeros((self.max_slots,), np.int32)
        self._eos = np.full((self.max_slots,), -1, np.int32)
        self._free: List[int] = list(range(self.max_slots))
        self._active: Dict[int, EngineRequest] = {}
        self._queue: "collections.deque[EngineRequest]" = collections.deque()
        # completed since the last run() drain -- the engine does NOT
        # retain request history beyond that (a long-lived engine would
        # otherwise hold every prompt/generation ever served); callers
        # keep their own handles
        self._done: List[EngineRequest] = []
        self._next_id = 0
        self._admit_counter = 0

        # jitted functions are owned by the Servable and shared across its
        # engines: one decode executable per max_slots shape (and per fused
        # window length K), one prefill trace per bucket length, warm for
        # the engine's whole lifetime (and the next engine's). The decode
        # cache argument is donated, so the hot loop never copies the slot
        # caches.
        # under a mesh, every jit the cache flows through pins its output
        # to the engine cache's placement: decode windows, insertion and
        # retirement then keep ONE canonical sharded layout end to end --
        # donation stays usable (no per-step copies) and the cache never
        # gathers to one device (let alone host) across a request's
        # lifetime. engine_fns shares executables across engines in both
        # modes (per cache-sharding tree under a mesh).
        out_sh = None if self.mesh is None else \
            jax.tree_util.tree_map(lambda x: x.sharding, self.cache)
        (self._decode, self._decode_many, self._write_slot,
         self._free_slot) = servable.engine_fns(out_sh)
        self._prefill = servable._engine_prefill_fn()
        if self.kv_layout == "paged":
            (self._write_paged, self._restore_paged,
             self._suffix_prefill) = servable.paged_engine_fns(out_sh)
        elif self._chunking:
            # dense chunked prefill rides the same suffix entry point the
            # paged prefix-hit path uses (servable.suffix_prefill_fn)
            self._suffix_prefill = servable.suffix_prefill_fn(out_sh)

    def _build_cache(self):
        """A fresh all-slots-free engine cache (constructor AND the
        recovery path after a decode-window failure invalidated the donated
        buffers)."""
        if self.cfg.family == "audio":
            # structure-only cache: encode batch-1 zero frames and broadcast
            # the slot axis (axis 1; every leaf is layer-stacked) -- the real
            # cross K/V arrives per request via write_slot at admission
            one = model_api.init_cache(
                self.servable.params, self.cfg, 1, self.cache_len,
                frames=jnp.zeros((1, self.cfg.n_audio_ctx, self.cfg.d_model),
                                 self.cfg.jdtype))
            cache = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(
                    x, x.shape[:1] + (self.max_slots,) + x.shape[2:]), one)
        else:
            cache = model_api.init_cache(self.servable.params, self.cfg,
                                         self.max_slots, self.cache_len,
                                         paged=self._layout)
        if self.mesh is not None:
            # mesh-first cache: slots over "data", heads/state over "model".
            # Lifecycle ops below are pinned to these shardings, so alloc/
            # free/reset/write never regather the cache (tested:
            # tests/test_sharded_serving.py)
            cache = model_api.shard_cache(cache, self.cfg, self.mesh)
        return cache

    # -- submission -------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 16, *,
               eos_id: Optional[int] = None, frames=None, priority: int = 0,
               deadline_s: Optional[float] = None,
               on_token: Optional[Callable[[int, int], None]] = None,
               on_done: Optional[Callable[[int, List[int]], None]] = None
               ) -> EngineRequest:
        """Enqueue a request; returns its handle (``.tokens`` fills as the
        engine runs, ``.status`` reaches exactly one terminal state).

        Invalid requests are REJECTED AT SUBMISSION with a structured
        reason (``status == 'failed'``, ``failure.code == 'rejected'``)
        instead of failing late inside prefill/decode -- submit() never
        raises for request-level problems. ``deadline_s`` is a relative
        wall-clock budget enforced at window-sync points (and, with a
        ``SchedSpec``, fast-failed at admission when the engine's measured
        rates already rule the deadline out); ``priority`` orders admission
        and arms preemption (higher wins)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        req = EngineRequest(req_id=self._next_id, prompt=prompt,
                            max_new_tokens=int(max_new_tokens), eos_id=eos_id,
                            frames=frames, on_token=on_token, on_done=on_done,
                            priority=int(priority))
        req.submitted_at = time.monotonic()
        self._next_id += 1
        if deadline_s is not None:
            req.deadline_at = req.submitted_at + float(deadline_s)

        reject = None
        if prompt.size == 0:
            reject = "empty prompt"
        elif max_new_tokens < 1:
            reject = ("max_new_tokens must be >= 1 (the prefill already "
                      "samples the first token)")
        elif prompt.size + max_new_tokens > self.cache_len:
            reject = (f"prompt ({prompt.size}) + max_new_tokens "
                      f"({max_new_tokens}) exceeds cache_len "
                      f"({self.cache_len})")
        elif self.cfg.family == "audio" and frames is None:
            reject = "audio requests need encoder frames"
        if reject is not None:
            self.stats.rejected += 1
            self._finalize(req, "failed",
                           FailureReason(FailureReason.REJECTED, reject))
            return req

        # deadline fast-fail AT ADMISSION (docs/API.md §SLO scheduling): an
        # already-expired deadline always fails here; with sched.fast_fail,
        # a completion projected past the deadline from the engine's
        # MEASURED prefill/decode rates fails too -- either way before the
        # request consumes a prefill slot. Both count as deadline_misses.
        if req.deadline_at is not None:
            now = time.monotonic()
            if now > req.deadline_at:
                self._finalize(req, "failed", FailureReason(
                    FailureReason.DEADLINE, "deadline expired at submission"))
                return req
            if self.sched is not None and self.sched.fast_fail:
                est = self._service_estimate_s(req)
                if est is not None and now + est > req.deadline_at:
                    self._finalize(req, "failed", FailureReason(
                        FailureReason.DEADLINE,
                        f"projected completion in {est:.3f}s exceeds the "
                        f"deadline (measured prefill/decode rates)"))
                    return req

        if self._chaos is not None:
            # open-loop ingest chaos: an action may re-entrantly submit a
            # burst through this engine; an exception sheds ONLY this
            # submission with a structured reason (never a crash)
            try:
                self._chaos.fire(chaos_mod.SITE_ARRIVAL_BURST, engine=self,
                                 request=req)
            except Exception as e:  # noqa: BLE001 -- shed, keep serving
                self._finalize(req, "shed", FailureReason(
                    FailureReason.OVERLOAD,
                    f"shed at ingest: {type(e).__name__}: {e}"))
                return req

        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            if self.overflow == "block":
                # drive the engine until the queue drains below the bound
                while len(self._queue) >= self.max_queue and self.step():
                    pass
            if len(self._queue) >= self.max_queue:
                if self.overflow == "shed-oldest":
                    victim = self._queue.popleft()
                    self._finalize(victim, "shed", FailureReason(
                        FailureReason.QUEUE_FULL,
                        "shed by newer submission (shed-oldest)"))
                else:                   # 'reject' (or a block that stalled)
                    self._finalize(req, "shed", FailureReason(
                        FailureReason.QUEUE_FULL,
                        f"queue full ({self.max_queue}), policy "
                        f"{self.overflow!r}"))
                    return req
        self._queue.append(req)
        self._shed_overload()
        return req

    def cancel(self, req: EngineRequest) -> bool:
        """Request cancellation of ``req``. Queued requests cancel
        immediately; active ones at the next window-sync point (already
        generated tokens stay on the handle). Returns False when the
        request is already terminal."""
        if req.status in TERMINAL_STATES:
            return False
        req.cancel_requested = True
        if req.status == "queued":
            try:
                self._queue.remove(req)
            except ValueError:      # pragma: no cover - defensive
                return False
            self._finalize(req, "cancelled", FailureReason(
                FailureReason.CANCELLED, "cancelled while queued"))
        return True

    # -- SLO estimation + overload degradation ----------------------------
    def _service_estimate_s(self, req: EngineRequest) -> Optional[float]:
        """Projected seconds to finish ``req``, from the engine's MEASURED
        prefill/decode rates (the EngineStats wall-clock buckets). Returns
        None until both rates have real samples -- estimation never
        guesses, so a cold engine neither fast-fails nor sheds."""
        st = self.stats
        if (st.prefill_s <= 0 or st.prefilled_tokens <= 0
                or st.decode_s <= 0 or st.steps <= 0):
            return None
        pre_tokens = req.prompt.size + req.n_generated - req.prefill_pos
        pre = pre_tokens / (st.prefilled_tokens / st.prefill_s)
        dec = (req.max_new_tokens - req.n_generated) \
            / (st.steps / st.decode_s)
        return max(pre, 0.0) + max(dec, 0.0)

    def _shed_overload(self) -> None:
        """Graceful overload degradation (``sched.max_queue_delay_s > 0``):
        when the estimated time to drain the queue exceeds the bound, shed
        queued requests -- lowest priority first, newest first within a
        class -- with the structured OVERLOAD reason until the backlog
        fits. Shedding the newest lowest-priority traffic keeps requests
        that already waited (and higher SLO tiers) on track instead of
        letting every request miss a little."""
        if (self.sched is None or self.sched.max_queue_delay_s <= 0
                or not self._queue):
            return
        ests: Dict[int, float] = {}
        for r in self._queue:
            est = self._service_estimate_s(r)
            if est is None:         # rates not measured yet: never shed
                return
            ests[r.req_id] = est
        bound = self.sched.max_queue_delay_s
        slots = max(1, self.max_slots)
        backlog = sum(ests.values()) / slots
        while backlog > bound and self._queue:
            victim = min(self._queue,
                         key=lambda r: (r.priority, -r.submitted_at))
            self._queue.remove(victim)
            backlog -= ests[victim.req_id] / slots
            self._finalize(victim, "shed", FailureReason(
                FailureReason.OVERLOAD,
                f"estimated queue delay exceeds "
                f"max_queue_delay_s={bound}"))

    def _on_stall(self, label: str, elapsed: float) -> None:
        """Watchdog callback (daemon thread): snapshot queue/active/chunk
        state into ``stats_dict()['watchdog']`` -- best-effort shallow
        reads, since the serving thread keeps mutating -- then forward to
        the user's ``on_stall``. Detection-only semantics unchanged."""
        try:
            now = time.monotonic()

            def row(r):
                return {"req_id": r.req_id, "status": r.status,
                        "pos": int(r.pos),
                        "prefill_pos": int(r.prefill_pos),
                        "prefill_target": int(r.prefill_target),
                        "n_generated": r.n_generated,
                        "age_s": round(now - r.submitted_at, 4)}

            self._watchdog_snapshot = {
                "site": label, "elapsed_s": round(elapsed, 4),
                "n_queued": len(self._queue),
                "n_active": len(self._active),
                "queued": [row(r) for r in list(self._queue)[:8]],
                "active": [row(r) for r in list(self._active.values())[:8]]}
        except Exception:  # pragma: no cover -- racing the serving thread
            self._watchdog_snapshot = {"site": label,
                                       "elapsed_s": round(elapsed, 4)}
        if self._user_on_stall is not None:
            self._user_on_stall(label, elapsed)

    # -- prefill ----------------------------------------------------------
    def _bucket(self, length: int) -> int:
        b = max(self.min_bucket, 1 << (length - 1).bit_length())
        return min(b, self.cache_len)

    def _admit(self, req: EngineRequest) -> bool:
        """Admit ``req`` into a free slot. Returns True when the request was
        CONSUMED (now active, or terminally failed) and False when it was
        PARKED back at the queue front by paged backpressure -- the
        scheduler must stop admitting for this sync point, or it would spin
        on the same exhausted pool."""
        if self.kv_layout == "paged":
            return self._admit_paged(req)
        return self._admit_dense(req)

    def _activate(self, req: EngineRequest, slot: int, pos: int,
                  pages: Optional[List[int]] = None) -> None:
        """Common admission bookkeeping (dense and paged paths)."""
        req.slot, req.pos = slot, pos
        req.status = "active"
        req.admit_seq = self._admit_counter
        self._admit_counter += 1
        self._active[slot] = req
        self._eos[slot] = -1 if req.eos_id is None else int(req.eos_id)
        if pages is not None:
            self._slot_pages[slot] = pages

    def _admit_dense(self, req: EngineRequest) -> bool:
        """Prefill ``req`` into a free slot. A resumed (preempted) request
        prefills over prompt + already-generated tokens, continuing exactly
        where it stopped. Any failure here fails ONLY this request: the
        slot is restored and the engine keeps serving."""
        slot, err = None, None
        with self._admit_span(req) as sp:
            try:
                if self._chaos is not None:
                    self._chaos.fire(chaos_mod.SITE_ALLOC, engine=self,
                                     request=req)
                slot = self._free.pop(0)
                seq = req.prompt if not req.tokens else np.concatenate(
                    [req.prompt, np.asarray(req.tokens, np.int32)])
                length = int(seq.size)
                bucket = self._bucket(length)

                if self._chaos is not None:
                    self._chaos.fire(chaos_mod.SITE_PREFILL, engine=self,
                                     request=req)
                if self.cfg.family == "audio":
                    sub = model_api.init_cache(
                        self.servable.params, self.cfg, 1, self.cache_len,
                        frames=jnp.asarray(req.frames)[None]
                        if np.ndim(req.frames) == 2
                        else jnp.asarray(req.frames))
                else:
                    sub = self._sub_template
                toks = np.zeros((bucket,), np.int32)
                toks[:length] = seq
                pos_seq = np.full((bucket,), -1, np.int32)
                pos_seq[:length] = np.arange(length)
                sub, logits, moe = self._prefill(self.servable.params, sub,
                                                 jnp.asarray(toks),
                                                 jnp.asarray(pos_seq),
                                                 jnp.int32(length))
                self.cache = self._write_slot(self.cache, jnp.int32(slot),
                                              sub)
                row = np.asarray(logits[length - 1])  # once per admission
            except Exception as e:  # noqa: BLE001 -- isolate to this request
                self._restore_slot(slot)
                err = e
            else:
                sp.set(tokens=length)
                self._count_moe(sp, moe)
                self.stats.prefills += 1
                self.stats.prefilled_tokens += length
                self.stats.bucket_hits[bucket] += 1
                finite = bool(np.all(np.isfinite(row)))
                if finite:
                    self._activate(req, slot, length)
                    tok = sample_token_row(row, self._key, slot, length - 1,
                                           temperature=self.temperature,
                                           top_k=self.top_k)
                else:
                    # poisoned before the first decode: quarantine at
                    # admission
                    self.cache = self._free_slot(self.cache, jnp.int32(slot))
                    self._restore_slot(slot)
        if err is not None:
            self._fail_admission(req, err)
        elif not finite:
            self._finalize(req, "failed", FailureReason(
                FailureReason.NONFINITE_LOGITS,
                f"non-finite prefill logits at position {length - 1}"))
        else:
            self._emit(req, int(tok), row)
        return True

    @contextlib.contextmanager
    def _admit_span(self, req: EngineRequest):
        """The ``repro.engine.admit`` span of one admission or prefill pass
        of ``req``, timed into ``stats.prefill_s``; ``tokens``, set at exit,
        the tokens prefilled. The pass that makes a never-preempted request
        active for the first time also carries ``wait_us``, its time in the
        queue: a parked attempt, a later chunk and a resume carry none."""
        first = (req.status != "active" and not req.tokens
                 and req.n_preempted == 0)
        wait = time.monotonic() - req.submitted_at
        with timed(self.stats, "prefill_s", "repro.engine.admit",
                   req=req.req_id, tokens=0) as sp:
            yield sp
            if first and req.status == "active":
                sp.set(wait_us=int(wait * 1e6))

    def _fail_admission(self, req: EngineRequest, err: Exception) -> None:
        log.warning("admission of request %d failed (%s: %s)",
                    req.req_id, type(err).__name__, err)
        self._finalize(req, "failed", FailureReason(
            FailureReason.PREFILL_ERROR, f"{type(err).__name__}: {err}"))

    def _park_or_fail(self, req: EngineRequest,
                      err: PagePoolExhausted) -> bool:
        """Paged backpressure: park ``req`` at the queue FRONT while active
        requests can still release pages (False, the ``_admit`` contract),
        else fail it (True)."""
        if self._active:
            req.status = "queued"
            self._queue.appendleft(req)
            log.info("parking request %d on page pressure (%s)",
                     req.req_id, err)
            return False
        self._finalize(req, "failed", FailureReason(
            FailureReason.KV_PAGES,
            f"{err} with no active requests to drain"))
        return True

    def _page_row(self, pages: List[int]):
        """A slot's page-table row: ``pages`` padded to table width with -1
        (-1 = unmapped; device scatters drop writes to unmapped pages)."""
        row = np.full((self._table_width,), -1, np.int32)
        row[:len(pages)] = pages
        return jnp.asarray(row)

    def _reserve_pages(self, n: int) -> List[int]:
        """Claim ``n`` fresh pages, evicting LRU prefix-cache references
        when the free list runs short (forfeits future hits, never touches
        an active slot's pages). Raises PagePoolExhausted -- the paged
        backpressure signal -- when eviction cannot cover the request."""
        if self._chaos is not None:
            self._chaos.fire(chaos_mod.SITE_PAGE_ALLOC, engine=self, want=n)
        while self._pool.free_count < n and self._prefix_cache.evict(1):
            pass
        return self._pool.alloc(n)

    def _admit_paged(self, req: EngineRequest) -> bool:
        """Paged admission: reserve ceil((len + max_new) / page_size) pages
        up front (the page table is static across decode windows), serve
        the longest cached prefix from shared pages, prefill only the
        remainder, and publish the fresh prompt's full pages for future
        sharers. A preempted request whose pages were retained re-attaches
        them with NO prefill at all. Pool exhaustion is backpressure (park
        at the queue front / structured shed), never a crash."""
        slot, err = None, None
        held: List[int] = []            # pages owned by THIS admission
        with self._admit_span(req) as sp:
            try:
                if self._chaos is not None:
                    self._chaos.fire(chaos_mod.SITE_ALLOC, engine=self,
                                     request=req)
                slot = self._free.pop(0)

                saved = self._saved_pages.pop(req.req_id, None)
                if saved is not None:
                    # preempt-resume via page retention: the victim's pages
                    # were never released, so restoring the page table +
                    # pos map resumes it bit-exactly with zero prefill work
                    pages, resume_len = saved
                    held = pages
                    self.cache = self._restore_paged(
                        self.cache, jnp.int32(slot), self._page_row(pages),
                        jnp.int32(resume_len))
                    self._activate(req, slot, resume_len, pages)
                    self._tokens[slot, 0] = req.tokens[-1]
                    self._pos[slot] = resume_len
                    self._remaining[slot] = \
                        req.max_new_tokens - req.n_generated
                    self.stats.page_resumes += 1
                    return True

                seq = req.prompt if not req.tokens else np.concatenate(
                    [req.prompt, np.asarray(req.tokens, np.int32)])
                length = int(seq.size)
                need = pages_needed(
                    min(length + req.max_new_tokens, self.cache_len),
                    self.kv_page_size)
                shared: List[int] = []
                if self._can_share and not req.tokens:
                    # cap the match at length-1 so a fully-cached prompt
                    # still prefills >= 1 suffix token (the forward pass
                    # must have a position to produce next-token logits
                    # from)
                    shared = self._prefix_cache.match(seq, limit=length - 1)
                    held = held + shared
                start = len(shared) * self.kv_page_size
                fresh = self._reserve_pages(need - len(shared))
                held = held + fresh
                pages = shared + fresh

                if self._chaos is not None:
                    self._chaos.fire(chaos_mod.SITE_PREFILL, engine=self,
                                     request=req)
                if start > 0:
                    # prefix hit: attach the pages, then prefill ONLY the
                    # suffix against the resident shared prefix (masked
                    # attention; write positions never land in shared full
                    # pages, so sharing is copy-on-write by construction)
                    suffix = seq[start:]
                    slen = int(suffix.size)
                    bucket = self._bucket(slen)
                    toks = np.zeros((bucket,), np.int32)
                    toks[:slen] = suffix
                    self.cache = self._restore_paged(
                        self.cache, jnp.int32(slot), self._page_row(pages),
                        jnp.int32(start))
                    self.cache, logits, moe = self._suffix_prefill(
                        self.servable.params, self.cache, jnp.asarray(toks),
                        jnp.int32(slot), jnp.int32(start), jnp.int32(slen))
                    row = np.asarray(logits[slen - 1])
                    self.stats.prefix_hit_tokens += start
                    self.stats.prefilled_tokens += slen
                else:
                    slen = length
                    bucket = self._bucket(length)
                    toks = np.zeros((bucket,), np.int32)
                    toks[:length] = seq
                    pos_seq = np.full((bucket,), -1, np.int32)
                    pos_seq[:length] = np.arange(length)
                    sub, logits, moe = self._prefill(
                        self.servable.params, self._sub_template,
                        jnp.asarray(toks), jnp.asarray(pos_seq),
                        jnp.int32(length))
                    self.cache = self._write_paged(
                        self.cache, jnp.int32(slot), sub,
                        self._page_row(pages))
                    row = np.asarray(logits[length - 1])
                    self.stats.prefilled_tokens += length
                    if self._can_share and not req.tokens:
                        # publish the prompt's FULL pages (strictly below
                        # the prompt length -- the partial tail page is
                        # mutable)
                        self._prefix_cache.insert(
                            seq, pages[:length // self.kv_page_size])
            except Exception as e:  # noqa: BLE001 -- isolate to this request
                # PagePoolExhausted included: backpressure, below
                if held:
                    self._pool.release(held)
                self._restore_slot(slot)
                err = e
            else:
                sp.set(tokens=slen)
                self._count_moe(sp, moe)
                self.stats.prefills += 1
                self.stats.bucket_hits[bucket] += 1
                finite = bool(np.all(np.isfinite(row)))
                if finite:
                    self._activate(req, slot, length, pages)
                    tok = sample_token_row(row, self._key, slot, length - 1,
                                           temperature=self.temperature,
                                           top_k=self.top_k)
                else:
                    self._pool.release(held)
                    self.cache = self._free_slot(self.cache, jnp.int32(slot))
                    self._restore_slot(slot)
        if isinstance(err, PagePoolExhausted):
            return self._park_or_fail(req, err)
        if err is not None:
            self._fail_admission(req, err)
        elif not finite:
            self._finalize(req, "failed", FailureReason(
                FailureReason.NONFINITE_LOGITS,
                f"non-finite prefill logits at position {length - 1}"))
        else:
            self._emit(req, int(tok), row)
        return True

    # -- chunked prefill (docs/API.md §SLO scheduling) --------------------
    def _begin_chunked(self, req: EngineRequest) -> bool:
        """Claim a slot (and, paged, the request's full page reservation +
        prefix match) WITHOUT running prefill compute -- chunk dispatch is
        metered separately by the token budget (``_prefill_chunk``). The
        request becomes active with ``_pos[slot]`` still -1: it holds its
        slot across windows but is a device no-op row until the final
        chunk samples its first token. Returns False when paged
        backpressure parked it at the queue front (the ``_admit``
        contract)."""
        slot, err = None, None
        held: List[int] = []
        with self._admit_span(req):
            try:
                if self._chaos is not None:
                    self._chaos.fire(chaos_mod.SITE_ALLOC, engine=self,
                                     request=req)
                slot = self._free.pop(0)

                if self.kv_layout == "paged":
                    saved = self._saved_pages.pop(req.req_id, None)
                    if saved is not None:
                        # preempt-resume page retention: instant, no
                        # prefill
                        pages, resume_len = saved
                        held = pages
                        self.cache = self._restore_paged(
                            self.cache, jnp.int32(slot),
                            self._page_row(pages), jnp.int32(resume_len))
                        self._activate(req, slot, resume_len, pages)
                        req.prefill_pos = req.prefill_target = 0
                        self._tokens[slot, 0] = req.tokens[-1]
                        self._pos[slot] = resume_len
                        self._remaining[slot] = \
                            req.max_new_tokens - req.n_generated
                        self.stats.page_resumes += 1
                        return True

                seq = req.prompt if not req.tokens else np.concatenate(
                    [req.prompt, np.asarray(req.tokens, np.int32)])
                length = int(seq.size)
                start = 0
                pages = None
                if self.kv_layout == "paged":
                    need = pages_needed(
                        min(length + req.max_new_tokens, self.cache_len),
                        self.kv_page_size)
                    shared: List[int] = []
                    if self._can_share and not req.tokens:
                        shared = self._prefix_cache.match(seq,
                                                          limit=length - 1)
                        held = held + shared
                    start = len(shared) * self.kv_page_size
                    fresh = self._reserve_pages(need - len(shared))
                    held = held + fresh
                    pages = shared + fresh
                    # install the page table up front: every chunk scatters
                    # through it, and the pos map starts at the shared
                    # prefix
                    self.cache = self._restore_paged(
                        self.cache, jnp.int32(slot), self._page_row(pages),
                        jnp.int32(start))
                    if start > 0:
                        self.stats.prefix_hit_tokens += start
                    elif self._can_share and not req.tokens:
                        self._pending_publish.add(req.req_id)
            except Exception as e:  # noqa: BLE001 -- isolate to this request
                if held:
                    self._pool.release(held)
                self._restore_slot(slot)
                err = e
            else:
                self._activate(req, slot, length, pages)
                req.prefill_pos = start
                req.prefill_target = length
        if isinstance(err, PagePoolExhausted):
            return self._park_or_fail(req, err)
        if err is not None:
            self._fail_admission(req, err)
        return True

    def _prefill_chunk(self, req: EngineRequest, budget: int) -> int:
        """Run prefill chunks for an admitted, partially-prefilled request
        until its prompt is resident or ``budget`` tokens are spent;
        returns the tokens dispatched. Chunk lengths are quantized to
        ``_chunk_len`` (or the prompt tail) and bucketed like one-shot
        prefills, so the suffix jit set stays small and warm. The final chunk
        samples the first token (the request decodes next window). A
        failure fails ONLY this request: the chaos site fires before the
        (cache-donating) suffix dispatch, so ``engine.cache`` survives an
        injected chunk fault intact (tests/test_chaos.py)."""
        if budget <= 0 or req.prefill_pos >= req.prefill_target:
            return 0
        seq = req.prompt if not req.tokens else np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)])
        used, row, err = 0, None, None
        moe, chunks = self._moe_carry.pop(req.req_id, (None, 0))
        with self._admit_span(req) as sp:
            try:
                while req.prefill_pos < req.prefill_target:
                    start = req.prefill_pos
                    c = min(self._chunk_len, req.prefill_target - start)
                    if c > budget - used:
                        break           # whole-chunk budget gating: defer
                    if self._chaos is not None:
                        self._chaos.fire(chaos_mod.SITE_PREFILL_CHUNK,
                                         engine=self, request=req,
                                         start=start, size=c)
                    bucket = self._bucket(c)
                    toks = np.zeros((bucket,), np.int32)
                    toks[:c] = seq[start:start + c]
                    if self._watchdog is not None:
                        self._watchdog.arm("prefill-chunk")
                    try:
                        self.cache, logits, rows = self._suffix_prefill(
                            self.servable.params, self.cache,
                            jnp.asarray(toks), jnp.int32(req.slot),
                            jnp.int32(start), jnp.int32(c))
                        if self._moe_slots:
                            moe = rows if moe is None else moe + rows
                            chunks += 1
                    finally:
                        if self._watchdog is not None:
                            self._watchdog.disarm()
                            self.stats.watchdog_stalls = \
                                len(self._watchdog.stalls)
                    req.prefill_pos += c
                    used += c
                    self.stats.prefilled_tokens += c
                    self.stats.prefill_chunks += 1
                    self.stats.bucket_hits[bucket] += 1
                if req.prefill_pos >= req.prefill_target:
                    row = np.asarray(logits[c - 1])
            except Exception as e:  # noqa: BLE001 -- isolate to this request
                err = e
            sp.set(tokens=used)
            if row is not None:
                self._count_moe(sp, moe, chunks)
            elif err is None and chunks:
                # read at the sync of the pass that ends the prompt
                self._moe_carry[req.req_id] = (moe, chunks)
        if err is not None:
            log.warning("chunked prefill of request %d failed (%s: %s)",
                        req.req_id, type(err).__name__, err)
            self._finalize(req, "failed", FailureReason(
                FailureReason.PREFILL_ERROR, f"{type(err).__name__}: {err}"))
            return used
        if row is None:
            return used                     # budget spent mid-prompt

        self.stats.prefills += 1
        if not np.all(np.isfinite(row)):
            self._finalize(req, "failed", FailureReason(
                FailureReason.NONFINITE_LOGITS,
                f"non-finite prefill logits at position "
                f"{req.prefill_target - 1}"))
            return used
        if req.req_id in self._pending_publish:
            self._pending_publish.discard(req.req_id)
            pages = self._slot_pages.get(req.slot, [])
            self._prefix_cache.insert(
                seq, pages[:req.prefill_target // self.kv_page_size])
        tok = sample_token_row(row, self._key, req.slot,
                               req.prefill_target - 1,
                               temperature=self.temperature,
                               top_k=self.top_k)
        self._emit(req, int(tok), row)
        return used

    def _admit_budgeted(self, req: EngineRequest, budget: int):
        """Admission dispatch for the chunked scheduler: prompts that fit
        in ONE chunk (the short/interactive population an SLO protects)
        take the LEGACY one-shot path -- donated slot write, paged prefix
        match, no full-cache chunk attention -- because slicing only pays
        off when a prompt spans windows. Multi-chunk prompts go through
        ``_admit_chunked``. Returns ``(consumed, tokens_used)``."""
        need = req.prompt.size + req.n_generated
        if need <= self._chunk_len:
            return self._admit(req), need
        return self._admit_chunked(req, budget)

    def _admit_chunked(self, req: EngineRequest, budget: int):
        """Chunked admission: claim slot + pages, then spend up to
        ``budget`` prefill tokens. Returns ``(consumed, tokens_used)``;
        ``consumed`` False = paged backpressure parked the request (the
        scheduler must stop admitting this sync point)."""
        if not self._begin_chunked(req):
            return False, 0
        if req.status != "active":          # begin failed terminally
            return True, 0
        if req.prefill_pos >= req.prefill_target:   # page-retention resume
            return True, 0
        return True, self._prefill_chunk(req, budget)

    def _restore_slot(self, slot: Optional[int]) -> None:
        """Return a popped-but-unoccupied slot to the free list."""
        if slot is not None and slot not in self._free:
            self._free.append(slot)
            self._free.sort()

    # -- lifecycle --------------------------------------------------------
    def _emit(self, req: EngineRequest, tok: int, logits_row=None) -> None:
        """Record one sampled token and retire the request if it just
        completed. ``logits_row`` (V,) is only materialized on host when
        the engine collects logits."""
        req.tokens.append(tok)
        if req.first_token_at is None:
            req.first_token_at = time.monotonic()
        if self.collect_logits and logits_row is not None:
            req.step_logits.append(np.asarray(logits_row, np.float32))
        self.stats.tokens_generated += 1
        if req.on_token is not None:
            req.on_token(req.req_id, tok)
        if (req.n_generated >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id)):
            self._finalize(req, "done")
        else:
            self._tokens[req.slot, 0] = tok
            self._pos[req.slot] = req.pos
            self._remaining[req.slot] = req.max_new_tokens - req.n_generated

    def _release_slot(self, req: EngineRequest, *,
                      keep_pages: bool = False) -> None:
        """Free ``req``'s slot with full recycle hygiene: zero attention KV
        and recurrent state on device, reset the host mirrors, return the
        slot to the free list. Paged engines also settle the slot's page
        references -- released back to the pool, or (``keep_pages``,
        preemption retention) parked in ``_saved_pages`` with the resume
        position so re-admission can re-attach them prefill-free."""
        slot = req.slot
        if self.kv_layout == "paged":
            pages = self._slot_pages.pop(slot, [])
            if keep_pages and pages:
                # resume point: KV holds positions 0..req.pos-1 (the
                # current token's KV is written by its NEXT decode step)
                self._saved_pages[req.req_id] = (pages, req.pos)
            elif pages:
                self._pool.release(pages)
        self.cache = self._free_slot(self.cache, jnp.int32(slot))
        self._pos[slot] = -1
        self._tokens[slot, 0] = 0
        self._remaining[slot] = 0
        self._eos[slot] = -1
        del self._active[slot]
        self._free.append(slot)
        self._free.sort()
        req.slot = -1

    def _finalize(self, req: EngineRequest, status: str,
                  reason: Optional[FailureReason] = None) -> None:
        """Move ``req`` to its (single) terminal state, releasing its slot
        if it holds one."""
        if req.slot >= 0:
            self._release_slot(req)
        if self.kv_layout == "paged":
            # a retained (preempted) request dying while queued must give
            # its saved pages back -- cancel/deadline/shed paths
            saved = self._saved_pages.pop(req.req_id, None)
            if saved is not None:
                self._pool.release(saved[0])
        self._pending_publish.discard(req.req_id)
        self._moe_carry.pop(req.req_id, None)
        req.status = status
        req.failure = reason
        req.done = status == "done"
        req.finished_at = time.monotonic()
        if status == "done":
            self.stats.completed += 1
        elif status == "failed":
            self.stats.failed += 1
        elif status == "cancelled":
            self.stats.cancelled += 1
        elif status == "shed":
            self.stats.shed += 1
        if reason is not None and reason.code == FailureReason.DEADLINE:
            self.stats.deadline_misses += 1
        self._done.append(req)
        if status == "done" and req.on_done is not None:
            req.on_done(req.req_id, list(req.tokens))

    def _preempt(self, req: EngineRequest) -> None:
        """Evict an in-flight request: free its slot (recycle hygiene) and
        requeue it at the FRONT of its priority class. Re-admission resumes
        it via page retention when the layout allows (paged + every layer's
        state lives in pages: pure linear attn/MLA) -- bit-exact and
        prefill-free -- and otherwise via prefill over prompt + generated
        tokens."""
        keep = (self.kv_layout == "paged" and self._can_retain
                and req.n_generated > 0)
        self._release_slot(req, keep_pages=keep)
        # a half-prefilled victim (chunk scheduling) restarts its prefill
        # from scratch on re-admission -- its slot state is gone (retention
        # requires n_generated > 0, so it never kept pages either)
        self._pending_publish.discard(req.req_id)
        req.prefill_pos = req.prefill_target = 0
        req.status = "queued"
        req.n_preempted += 1
        self.stats.preemptions += 1
        self._queue.appendleft(req)

    def _sweep_control(self) -> None:
        """The window-sync control sweep: apply pending cancellations,
        expire deadlines for queued AND active requests, fast-fail queued
        requests whose projected completion already rules their deadline
        out (``sched.fast_fail``, measured rates only) and run overload
        shedding. Runs at the top of every step(), so lifecycle
        enforcement costs nothing between sync points (the fused window
        stays one jitted scan)."""
        now = time.monotonic()

        def expired(r):
            return r.deadline_at is not None and now > r.deadline_at

        fast = self.sched is not None and self.sched.fast_fail

        def doomed(r):
            if not fast or r.deadline_at is None:
                return False
            est = self._service_estimate_s(r)
            return est is not None and now + est > r.deadline_at

        for req in [r for r in self._queue
                    if r.cancel_requested or expired(r) or doomed(r)]:
            self._queue.remove(req)
            if req.cancel_requested:
                self._finalize(req, "cancelled", FailureReason(
                    FailureReason.CANCELLED, "cancelled while queued"))
            elif expired(req):
                self._finalize(req, "failed", FailureReason(
                    FailureReason.DEADLINE,
                    "deadline expired before admission"))
            else:
                self._finalize(req, "failed", FailureReason(
                    FailureReason.DEADLINE,
                    "projected completion exceeds deadline while queued "
                    "(fast-fail before consuming a prefill slot)"))
        for req in [r for r in self._active.values()
                    if r.cancel_requested or expired(r)]:
            if req.cancel_requested:
                self._finalize(req, "cancelled", FailureReason(
                    FailureReason.CANCELLED,
                    f"cancelled after {req.n_generated} tokens"))
            else:
                self._finalize(req, "failed", FailureReason(
                    FailureReason.DEADLINE,
                    f"deadline expired after {req.n_generated}/"
                    f"{req.max_new_tokens} tokens"))
        self._shed_overload()

    def _pop_next(self) -> EngineRequest:
        """Highest-priority queued request, FIFO within a priority class."""
        best_i, best = 0, self._queue[0]
        for i, req in enumerate(self._queue):
            if req.priority > best.priority:
                best_i, best = i, req
        del self._queue[best_i]
        return best

    def _schedule(self) -> None:
        """Admissions + priority preemption (a window-sync point action).
        A False from ``_admit`` means paged backpressure parked the request
        at the queue front -- stop admitting until the next sync point (the
        pool cannot satisfy it now; retrying in this loop would spin).
        Chunk-scheduling engines route through ``_schedule_chunked``."""
        if self._chunking:
            self._schedule_chunked()
            return
        while self._free and self._queue:
            if not self._admit(self._pop_next()):
                return
        # under slot pressure: strictly-higher-priority queued traffic
        # evicts the lowest-priority (latest-admitted on ties) active
        # request; the victim resumes later (page retention or re-prefill)
        while self._queue and not self._free and self._active:
            best_p = max(r.priority for r in self._queue)
            victim = min(self._active.values(),
                         key=lambda r: (r.priority, -r.admit_seq))
            if best_p <= victim.priority:
                break
            self._preempt(victim)
            if not self._admit(self._pop_next()):
                return

    def _schedule_chunked(self) -> None:
        """The token-budget scheduler (docs/API.md §SLO scheduling): each
        window-sync point spends at most ``sched.token_budget`` prefill
        tokens, in ``sched.max_chunk``-sized chunks, so one long prompt
        can never head-of-line-block running decodes behind a monolithic
        prefill. ``decode_priority`` reserves ``n_decoding * sync_every``
        of the budget for the decode window that follows; with nothing
        decoding the budget clamps to >= 1 token so prefill always makes
        progress (liveness). Partially-prefilled residents continue in
        admission order before new requests are admitted; priority
        preemption matches the legacy scheduler."""
        sched = self.sched
        budget = sched.token_budget if sched.token_budget > 0 else (1 << 30)
        n_dec = sum(1 for s in self._active if self._pos[s] >= 0)
        if sched.decode_priority:
            budget -= n_dec * self.sync_every
        if n_dec == 0:
            budget = max(budget, 1)

        # 1. priority preemption FIRST (the legacy policy): a high-SLO
        # arrival must not wait out a low-priority resident's chunked
        # prefill -- the continuation pass below would otherwise spend
        # every window's budget on the victim it is about to evict. The
        # preemptor claims its slot even at budget 0 (its chunks then run
        # in later windows).
        while self._queue and not self._free and self._active:
            best_p = max(r.priority for r in self._queue)
            victim = min(self._active.values(),
                         key=lambda r: (r.priority, -r.admit_seq))
            if best_p <= victim.priority:
                break
            self._preempt(victim)
            consumed, used = self._admit_budgeted(self._pop_next(), budget)
            budget -= used
            if not consumed:
                return

        # 2. admit new requests into free slots BEFORE continuing resident
        # prefills: a short arrival starts (and finishes) its prefill out
        # of the same budget a long resident would otherwise monopolize --
        # this is what kills head-of-line blocking. But admissions must
        # not STARVE the residents either (under sustained arrivals a long
        # prompt would otherwise never finish prefilling while holding its
        # slot): when a continuation is pending, admissions may spend at
        # most half the window budget, so the oldest resident keeps
        # making whole-chunk progress (set token_budget >= 2 * max_chunk
        # for both halves to fit a chunk).
        pending = any(r.prefill_pos < r.prefill_target
                      for r in self._active.values())
        adm_budget = budget // 2 if pending else budget
        while adm_budget > 0 and self._free and self._queue:
            consumed, used = self._admit_budgeted(self._pop_next(),
                                                  adm_budget)
            adm_budget -= used
            budget -= used
            if not consumed:
                return

        # 3. continue partially-prefilled residents, oldest admission first
        for req in sorted((r for r in self._active.values()
                           if r.prefill_pos < r.prefill_target),
                          key=lambda r: r.admit_seq):
            if budget <= 0:
                break
            budget -= self._prefill_chunk(req, budget)

    # -- stepping ---------------------------------------------------------
    def step(self) -> bool:
        """One window-sync cycle: control sweep (cancel/deadline/overload),
        schedule (chunk continuation + admit + preempt), then ONE batched
        decode window (up to ``sync_every`` fused steps) over the DECODING
        slots -- a mid-prefill request (chunk scheduling) holds its slot
        as a device no-op row (pos -1) and rides along untouched. Returns
        True while there is (or may be) work left."""
        self._sweep_control()
        self._schedule()
        decoding = sorted(s for s in self._active if self._pos[s] >= 0)
        if not decoding:
            return bool(self._active or self._queue)
        if self._watchdog is not None:
            self._watchdog.arm("decode-window")
        try:
            if self._chaos is not None:
                self._chaos.fire(chaos_mod.SITE_WINDOW, engine=self)
            k = min(self.sync_every,
                    max(int(self._remaining[s]) for s in decoding))
            if k <= 1:
                self._step_single(decoding)
            else:
                self._step_fused(k, decoding)
            if self._chaos is not None:
                self._chaos.fire(chaos_mod.SITE_SYNC, engine=self)
        except Exception as e:  # noqa: BLE001 -- keep the engine serving
            self._recover_window_failure(e)
        finally:
            if self._watchdog is not None:
                self._watchdog.disarm()
                self.stats.watchdog_stalls = len(self._watchdog.stalls)
        return bool(self._active or self._queue)

    def _recover_window_failure(self, err: Exception) -> None:
        """A decode window raised: the donated engine cache may be
        invalidated, so fail every active request with a structured reason,
        rebuild a fresh cache, and leave the engine usable (queued
        requests are admitted on the next step)."""
        log.warning("decode window failed (%s: %s); failing %d active "
                    "request(s) and rebuilding the engine cache",
                    type(err).__name__, err, len(self._active))
        reason = FailureReason(
            FailureReason.ENGINE_ERROR,
            f"decode window failed: {type(err).__name__}: {err}")
        reqs = list(self._active.values())
        self._active.clear()
        self._free = list(range(self.max_slots))
        self._pos[:] = -1
        self._tokens[:] = 0
        self._remaining[:] = 0
        self._eos[:] = -1
        if self.kv_layout == "paged":
            # the rebuilt cache has fresh (zeroed) pools: restart the host
            # allocator and drop every prefix/retention reference with it
            self._slot_pages.clear()
            self._saved_pages.clear()
            self._pool.reset()
            self._prefix_cache = PrefixCache(self._pool, self.kv_page_size)
        self.cache = self._build_cache()
        for req in reqs:
            req.slot = -1
            self._finalize(req, "failed", reason)

    def _step_single(self, decoding: List[int]) -> None:
        """The unfused loop: one decode, one host sync per token. Kept for
        ``sync_every=1`` and ``collect_logits`` (per-step logits only exist
        on host here). ``decoding`` is the slot set this window actually
        decodes -- mid-prefill slots are skipped at the drain (their rows
        are device no-ops and must not be quarantined or emitted)."""
        with self._window_span(1) as sp:
            self.stats.steps += 1
            self.stats.windows += 1
            self.stats.occupancy_sum += len(decoding)
            next_tok, ok, logits, self.cache, moe = self._decode(
                self.servable.params, self.cache, jnp.asarray(self._tokens),
                jnp.asarray(self._pos), self._key, self.temperature,
                self.top_k)
            toks = np.asarray(next_tok)         # (max_slots,) int32 only
            ok_h = np.asarray(ok)               # (max_slots,) bool
            rows = np.asarray(logits[:, 0, :]) if self.collect_logits \
                else None
            sp.set(tokens=int(ok_h[decoding].sum()))
            self._count_moe(sp, moe)
        with timed(self.stats, "sync_s", "repro.engine.sync"):
            for slot in decoding:
                req = self._active[slot]
                if not ok_h[slot]:
                    # non-finite logits: quarantine only this slot
                    self._finalize(req, "failed", FailureReason(
                        FailureReason.NONFINITE_LOGITS,
                        f"non-finite decode logits at position {req.pos}"))
                    continue
                req.pos += 1
                self._emit(req, int(toks[slot]),
                           rows[slot] if rows is not None else None)

    def _count_moe(self, sp, rows, forwards: int = 1) -> None:
        """Add the MoE ``rows`` of ``forwards`` forwards, read after the
        device call's own sync, to ``stats`` and to its span ``sp``."""
        if not self._moe_slots:
            return
        n, slots = int(np.asarray(rows).sum()), self._moe_slots * forwards
        self.stats.moe_rows += n
        self.stats.moe_expert_slots += slots
        sp.set(moe_rows=n, moe_expert_slots=slots)

    def _window_span(self, k: int):
        """The ``repro.engine.window`` span of one decode window of ``k``
        steps over ``max_slots`` rows, timed into ``stats.decode_s``;
        ``tokens``, set at exit, the tokens it emitted."""
        return timed(self.stats, "decode_s", "repro.engine.window", k=k,
                     slots=self.max_slots, tokens=0)

    def _step_fused(self, k: int, decoding: List[int]) -> None:
        """The fused hot loop: K decode steps inside one jitted scan
        (sampling, EOS, non-finite guard and position bookkeeping on
        device), then ONE host sync that drains the emitted tokens, fires
        callbacks in step order and recycles finished slots. ``k`` never
        exceeds the largest remaining budget, so a window cannot overshoot
        ``max_new_tokens``; slots that hit EOS (or their budget, or
        non-finite logits) mid-window deactivate themselves on device and
        ride along as no-ops until the sync."""
        with self._window_span(k) as sp:
            self.stats.steps += k
            self.stats.windows += 1
            toks, valid, state = self._decode_many(
                self.servable.params, self.cache, jnp.asarray(self._tokens),
                jnp.asarray(self._pos), jnp.asarray(self._remaining),
                jnp.asarray(self._eos), self._key, k, self.temperature,
                self.top_k)
            self.cache = state["cache"]
            toks_h = np.asarray(toks)               # (K, B) int32
            valid_h = np.asarray(valid)             # (K, B) bool
            failed_h = np.asarray(state["failed"])  # (B,) bool
            # writable host mirrors (np.asarray of a jax array is read-only)
            self._tokens = np.array(state["token"], np.int32)
            self._pos = np.array(state["pos"], np.int32)
            self._remaining = np.array(state["remaining"], np.int32)
            emitted = int(valid_h.sum())
            sp.set(tokens=emitted)
            self._count_moe(sp, state["moe_rows"], k)

        with timed(self.stats, "sync_s", "repro.engine.sync"):
            self.stats.occupancy_sum += emitted
            for step in range(k):
                for slot in decoding:
                    if not valid_h[step, slot]:
                        continue
                    req = self._active[slot]
                    req.pos += 1
                    tok = int(toks_h[step, slot])
                    req.tokens.append(tok)
                    self.stats.tokens_generated += 1
                    if req.on_token is not None:
                        req.on_token(req.req_id, tok)
            for slot in decoding:
                req = self._active[slot]
                if failed_h[slot]:              # device quarantined it
                    self._finalize(req, "failed", FailureReason(
                        FailureReason.NONFINITE_LOGITS,
                        f"non-finite decode logits in fused window at "
                        f"position {req.pos}"))
                elif self._pos[slot] < 0:       # device marked it finished
                    # _finalize re-zeroes the host mirrors; cache hygiene
                    # via free_slot as in the per-step path
                    self._finalize(req, "done")

    def run(self, max_steps: Optional[int] = None) -> List[EngineRequest]:
        """Drain the queue and all active slots; returns every request that
        reached a terminal state since the last drain (done / failed /
        cancelled / shed), in submission order, and releases them from
        engine tracking (callers keep their handles -- the engine itself
        retains no request history, so a long-lived engine's memory is
        bounded by its live requests)."""
        steps = 0
        while self.step():
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        done, self._done = self._done, []
        return sorted(done, key=lambda r: r.req_id)

    def close(self) -> None:
        """Stop the watchdog thread (idempotent; engines without one are
        no-ops)."""
        if self._watchdog is not None:
            self._watchdog.close()

    # -- chaos / test hooks ----------------------------------------------
    def corrupt_slot(self, slot: int) -> None:
        """Chaos hook: NaN-fill every float leaf of one slot's cache state
        (``repro.runtime.chaos.poison_slot``). The slot's next decode
        logits go non-finite and the engine's quarantine path must contain
        the damage to exactly this slot. Paged engines NaN-fill the slot's
        OWN pages instead (pool rows are not slot-addressable; co-resident
        slots never reference another slot's pages, so containment holds by
        the same argument). Only the slot's PRIVATE pages (refcount 1) are
        filled: shared prefix pages are other requests' state too, and
        poisoning them would break the containment the test asserts."""
        if self.kv_layout == "paged":
            own = [p for p in self._slot_pages.get(int(slot), [])
                   if self._pool.refcount(p) == 1]
            rows = jnp.asarray(own, jnp.int32)
            if rows.size == 0:
                return

            def poison(path, x):
                name = getattr(path[-1], "key", None)
                if not (isinstance(name, str) and name.endswith("_pages")
                        and jnp.issubdtype(x.dtype, jnp.floating)):
                    return x
                lead = getattr(path[0], "key", None) == "blocks"
                nan = jnp.nan
                if lead:
                    return x.at[:, rows].set(nan)
                return x.at[rows].set(nan)
            self.cache = jax.tree_util.tree_map_with_path(poison, self.cache)
            return
        sub = model_api.read_slot(self.cache, self.cfg, int(slot))
        sub = jax.tree_util.tree_map(
            lambda x: jnp.full_like(x, jnp.nan)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, sub)
        self.cache = self._write_slot(self.cache, jnp.int32(int(slot)), sub)

    def verify_invariants(self) -> None:
        """Assert the engine's internal bookkeeping is consistent (chaos
        suite: called after every injected fault). Raises AssertionError
        on violation; cheap enough for tests, not run on the hot path."""
        slots = sorted(self._free) + sorted(self._active)
        assert sorted(slots) == list(range(self.max_slots)), (
            f"slot leak: free={sorted(self._free)} "
            f"active={sorted(self._active)} of {self.max_slots}")
        assert len(set(self._free)) == len(self._free), (
            f"duplicate free slots: {self._free}")
        for slot, req in self._active.items():
            assert req.slot == slot and req.status == "active", (
                f"slot {slot} holds request {req.req_id} with "
                f"slot={req.slot} status={req.status}")
            assert (self._pos[slot] >= 0 or req.n_generated > 0
                    or 0 <= req.prefill_pos < req.prefill_target), (
                f"active slot {slot} has no progress")
        for slot in self._free:
            assert self._pos[slot] == -1, (
                f"free slot {slot} has live pos {self._pos[slot]}")
        for req in self._queue:
            assert req.status == "queued" and req.slot == -1, (
                f"queued request {req.req_id} has slot={req.slot} "
                f"status={req.status}")
        for req in self._done:
            assert req.status in TERMINAL_STATES and req.slot == -1, (
                f"drained request {req.req_id} non-terminal: {req.status}")
        if self.kv_layout == "paged":
            self._pool.check()
            assert set(self._slot_pages) == set(self._active), (
                f"page ownership out of sync with active slots: "
                f"{sorted(self._slot_pages)} vs {sorted(self._active)}")
            for slot, pages in self._slot_pages.items():
                for p in pages:
                    assert self._pool.refcount(p) >= 1, (
                        f"slot {slot} holds unreferenced page {p}")
            for req_id, (pages, _len) in self._saved_pages.items():
                for p in pages:
                    assert self._pool.refcount(p) >= 1, (
                        f"retained request {req_id} holds unreferenced "
                        f"page {p}")

    # -- introspection ----------------------------------------------------
    def kv_stats(self) -> Dict:
        """KV-memory scorecard (``stats_dict()['kv']``): layout, pool
        utilization and the prefix-sharing/retention counters. Byte figures
        come from the real device leaves at construction time."""
        if self.kv_layout != "paged":
            return {"layout": "dense",
                    "kv_bytes_total": int(self._kv_bytes_total),
                    "kv_bytes_per_slot":
                        int(self._kv_bytes_total) // self.max_slots,
                    "prefilled_tokens": self.stats.prefilled_tokens,
                    "prefix_hit_tokens": 0}
        pool = self._pool
        return {"layout": "paged",
                "page_size": self.kv_page_size,
                "n_pages": pool.n_pages,
                "pages_used": pool.used_count,
                "pages_free": pool.free_count,
                "peak_pages_used": pool.peak_used,
                "bytes_per_page": pool.bytes_per_page,
                "kv_bytes_total": pool.total_bytes(),
                "kv_bytes_used": pool.used_bytes(),
                "utilization": round(pool.used_count / pool.n_pages, 4),
                "prefix_cached_pages": self._prefix_cache.cached_pages,
                "prefix_hit_tokens": self.stats.prefix_hit_tokens,
                "prefilled_tokens": self.stats.prefilled_tokens,
                "page_resumes": self.stats.page_resumes}

    def stats_dict(self) -> Dict:
        """``EngineStats.as_dict()`` plus the ``'kv'`` section, the
        ``'quant'`` section when the servable carries quantized packs
        (pack bytes, compression ratio vs fp32, worst quantization
        error), and, after a watchdog stall, the ``'watchdog'`` snapshot
        of queue/active/chunk state taken at detection time -- last
        stall wins."""
        d = self.stats.as_dict()
        d["kv"] = self.kv_stats()
        qs = getattr(self.servable, "quant_stats", lambda: None)()
        if qs:
            d["quant"] = qs
        if self._watchdog_snapshot is not None:
            d["watchdog"] = dict(self._watchdog_snapshot)
        return d

    @property
    def n_active(self) -> int:
        return len(self._active)

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    @property
    def n_free(self) -> int:
        return len(self._free)
