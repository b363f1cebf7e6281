"""Measured backend selection for block-sparse serving (``backend='auto'``).

The Sparsity Roofline argument (arXiv:2310.00496) -- and this repo's own
``BENCH_kernels.json`` -- say the profitable (backend, tile, density)
region must be *measured on the target device*, not assumed: on the CPU
reference box dense wins most cells outright, ``gather`` overtakes it only
below ~10% density, and ``plan`` only at the paper's 32x1 linear tile.
A hardcoded ``default_backend()`` cannot express any of that. This module
micro-benchmarks the candidate execution paths

    dense       -- plain ``x @ w.T`` (the negative control / usual CPU winner)
    gather      -- one gather per stored tile (``bsr_linear`` backend)
    rowpack     -- row-grouped batched matmul, per-call scatter
    plan        -- precomputed RowPackPlan, data row-grouped offline
    pallas      -- flat-stream TPU kernel (native on TPU; interpret elsewhere)
    masked      -- dense-layout tile-skipping kernel (TPU)
    plan_pallas -- compiled plan-consuming kernel: the RowPackPlan's spill
                   schedule drives the Pallas grid (exec_plan, TPU)

Decode-side, :func:`choose_decode_kernel` runs the same machinery over the
attention decode step ('xla' materialized softmax vs the split-K 'flash'
kernel, kernels/flash_decode.py); its stub proxy charges the flash arm the
split-K reduce traffic (per-split on-chip (m, l, acc) state) so the
crossover moves with context length and split count.

per *pattern fingerprint* on the current device, picks the fastest, and
persists the winner so the cost is paid once per (pattern, device) --
across processes, not just per process.

Cache location and invalidation
-------------------------------
Winners live in ONE json file: ``$REPRO_AUTOTUNE_CACHE`` if set, else
``~/.cache/repro/autotune.json``. Each entry is keyed by
``sha1(pattern fingerprint) : m<batch rows> : <device kind> :
d<device count> : [shard tag :] <mode> : c<candidate-set digest>``, so a
different sparsity pattern, measurement batch size, device kind, *visible
device count*, shard partitioning, timing mode, or candidate set never
reuses a stale winner -- there is nothing else to invalidate. (The device
count and shard tag matter under mesh serving: a winner measured on one
device must not answer for an 8-way-sharded pack whose per-device shard
is an 8x smaller problem.) Delete the file (or point the env var
elsewhere) to force re-tuning.

The file carries a format ``version``; loading an older version silently
discards its entries (they were keyed without the device/shard fields) and
the next ``put`` rewrites the file at the current version -- stale caches
migrate by invalidation, never by crash.

Stub mode (CI determinism)
--------------------------
With ``REPRO_AUTOTUNE_STUB=1`` (or ``stub=True``) no wall-clock timing
runs: backends are ranked by a deterministic FLOP/traffic proxy, so
``backend='auto'`` paths are exercised reproducibly in CI. Tests can also
inject a frozen ``timer`` to exercise the wall-clock code path without
real clocks (tests/test_autotune.py).

Interpret-mode honesty: off-TPU, ``pallas`` and ``masked`` execute in
Pallas interpret mode -- a correctness vehicle thousands of times slower
than any serving path -- so wall-clock mode drops them from the candidate
set off-TPU rather than spending minutes proving they lose (docs/PERF.md).
The stub proxy still ranks them (with an interpret penalty), so their
dispatch path stays exercised.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import exec_plan as xp
from repro.kernels.bsr_matmul import (KernelBSR, masked_matmul,
                                      pallas_interpret_default,
                                      pallas_tile_supported)
from repro.kernels.flash_decode import default_kv_split, flash_decode_aligned

CANDIDATES = ("dense", "gather", "rowpack", "plan", "pallas", "masked",
              "plan_pallas")
#: quantized-pack arms, appended to the default candidate set only when
#: the caller serves quantized packs (choose_backend(quant=...)): int8
#: values + per-block scales through the dequant-fused plan matmul (XLA
#: composition / compiled Pallas kernel). Their stub costs price the
#: 4x-smaller value stream plus the scale stream, so 'auto' only picks
#: them where the reduced traffic actually pays.
QUANT_CANDIDATES = ("plan_q8", "plan_pallas_q8")
#: interpret-mode-only off TPU: excluded from wall-clock candidate sets
#: there (docs/PERF.md); the stub proxy still ranks them
INTERPRET_ONLY = ("pallas", "masked", "plan_pallas", "plan_pallas_q8")

#: attention decode-step kernels ranked by choose_decode_kernel
DECODE_CANDIDATES = ("xla", "flash")
#: decode kernels that run in interpret mode off-TPU
DECODE_INTERPRET_ONLY = ("flash",)

_ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
_ENV_STUB = "REPRO_AUTOTUNE_STUB"

#: on-disk winner-cache format. v1 keys lacked the device-count and shard
#: fields (a winner measured on 1 device would answer for 8); v1 files are
#: read as empty and rewritten at the current version on the next put.
CACHE_VERSION = 2


def stub_mode() -> bool:
    return os.environ.get(_ENV_STUB, "").strip() not in ("", "0", "false")


def device_kind() -> str:
    d = jax.devices()[0]
    return f"{d.platform}:{getattr(d, 'device_kind', '?')}".replace(" ", "_")


def pattern_digest(pack: KernelBSR) -> str:
    return hashlib.sha1(xp.kernel_pattern_fingerprint(pack)).hexdigest()[:16]


# --------------------------------------------------------------------------
# pack wrappers consumed by models/common.linear
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class BackendChoice:
    """A KernelBSR pattern pinned to a measured ``bsr_linear`` backend.

    ``prepare_servable(spec.backend='auto')`` stores these in ``packs``
    when the winner is a runtime-dispatch backend (gather / rowpack /
    pallas); the params tree keeps the packed ``(nnzt, bn, bk)`` values
    and ``models/common.linear`` routes through ``bsr_matmul`` with this
    backend instead of ``default_backend()``."""

    pack: KernelBSR
    backend: str

    @property
    def shape(self):
        return self.pack.shape

    @property
    def tile(self):
        return self.pack.tile

    @property
    def density(self) -> float:
        return self.pack.density

    @property
    def fingerprint(self) -> bytes:
        return (b"choice:" + self.backend.encode()
                + xp.kernel_pattern_fingerprint(self.pack))

    def __hash__(self):
        return hash(self.fingerprint)

    def __eq__(self, other):
        return (isinstance(other, BackendChoice)
                and self.fingerprint == other.fingerprint)


@dataclasses.dataclass(frozen=True, eq=False)
class MaskedPack:
    """Dense-layout serving through the tile-skipping ``masked`` kernel:
    the params tree keeps the DENSE ``(N, K)`` weight and only this static
    tile occupancy mask rides in ``packs`` (compute skipped, weight
    traffic paid -- the paper's format-support negative control)."""

    tile_mask: np.ndarray     # (R, C) bool, True = stored tile
    shape: Tuple[int, int]
    tile: Tuple[int, int]

    @property
    def density(self) -> float:
        return float(np.mean(self.tile_mask))

    @property
    def fingerprint(self) -> bytes:
        header = np.array([*self.shape, *self.tile], np.int64)
        return (b"masked:" + header.tobytes()
                + np.packbits(np.asarray(self.tile_mask, bool)).tobytes())

    def __hash__(self):
        return hash(self.fingerprint)

    def __eq__(self, other):
        return (isinstance(other, MaskedPack)
                and self.fingerprint == other.fingerprint)


def masked_pack_from(pack: KernelBSR) -> MaskedPack:
    mask = np.zeros((pack.n_brows, pack.n_bcols), bool)
    rows = np.asarray(pack.row_id[: pack.real_nnzt])
    cols = np.asarray(pack.col_id[: pack.real_nnzt])
    mask[rows, cols] = True
    return MaskedPack(tile_mask=mask, shape=pack.shape, tile=pack.tile)


def dense_from_pack(pack: KernelBSR, data=None) -> np.ndarray:
    """Densify a KernelBSR back to (N, K) -- the dense / masked candidate's
    weight. ``data`` defaults to the pack's stored values."""
    data = np.asarray(jax.device_get(pack.data if data is None else data))
    n, k = pack.shape
    bn, bk = pack.tile
    w = np.zeros((n // bn, bn, k // bk, bk), data.dtype)
    rows = np.asarray(pack.row_id[: pack.real_nnzt])
    cols = np.asarray(pack.col_id[: pack.real_nnzt])
    w[rows, :, cols, :] = data[: pack.real_nnzt]
    return w.reshape(n, k)


def shard_subpack(pack: KernelBSR, n_shards: int, axis: str) -> KernelBSR:
    """The measurement proxy for a tensor-parallel shard: the sub-pattern
    of the MOST occupied shard (the per-device straggler that sets the
    layer's critical path), as its own KernelBSR over the per-device
    sub-shape. ``axis='out'`` slices output block rows, ``'in'`` input
    block cols (serving/export.shard_axis_for conventions)."""
    from repro.kernels.bsr_matmul import pack_bsr
    rows = np.asarray(pack.row_id[: pack.real_nnzt], np.int64)
    cols = np.asarray(pack.col_id[: pack.real_nnzt], np.int64)
    per = (pack.n_brows if axis == "out" else pack.n_bcols) // n_shards
    shard_of = (rows if axis == "out" else cols) // per
    s = int(np.bincount(shard_of, minlength=n_shards).argmax())
    w = dense_from_pack(pack)
    bn, bk = pack.tile
    if axis == "out":
        sub = w[s * per * bn: (s + 1) * per * bn, :]
    else:
        sub = w[:, s * per * bk: (s + 1) * per * bk]
    return pack_bsr(sub, pack.tile)


# --------------------------------------------------------------------------
# the on-disk winner cache
# --------------------------------------------------------------------------

def default_cache_path() -> str:
    env = os.environ.get(_ENV_CACHE)
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "autotune.json")


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0


def _valid_entries(entries) -> Dict[str, dict]:
    """Keep only well-formed (str key -> dict record) winner entries; a
    hand-edited or bit-rotted file degrades to fewer cached winners, never
    to a crash in ``get``'s consumers."""
    if not isinstance(entries, dict):
        return {}
    return {k: v for k, v in entries.items()
            if isinstance(k, str) and isinstance(v, dict)}


class AutotuneCache:
    """Winner cache persisted as one JSON file (see module docstring for
    the key scheme / invalidation rules). Reads merge-on-write, so
    concurrent processes at worst re-measure -- they never corrupt; an
    unreadable/corrupt file reads as empty and is rewritten by the next
    ``put`` (tests/test_autotune.py)."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_cache_path()
        self.stats = CacheStats()
        self._entries: Optional[Dict[str, dict]] = None

    def _load(self) -> Dict[str, dict]:
        if self._entries is None:
            # any unreadable file -- missing, truncated mid-write, binary
            # garbage, wrong JSON shape -- reads as an EMPTY cache (worst
            # case: re-measure) and is replaced wholesale by the next
            # put(); a corrupt winner cache must never crash serving.
            # ValueError covers JSONDecodeError and UnicodeDecodeError.
            self._entries = {}
            try:
                with open(self.path) as f:
                    doc = json.load(f)
                # migration-by-invalidation: entries written under an older
                # key scheme (no device count / shard tag) are dropped, not
                # crashed on; the file is rewritten at CACHE_VERSION by the
                # next put()
                if isinstance(doc, dict) \
                        and doc.get("version") == CACHE_VERSION:
                    self._entries = _valid_entries(doc.get("entries"))
            except (OSError, ValueError):
                pass
        return self._entries

    def get(self, key: str) -> Optional[dict]:
        rec = self._load().get(key)
        if rec is None:
            self.stats.misses += 1
        else:
            self.stats.hits += 1
        return rec

    def put(self, key: str, record: dict) -> None:
        entries = self._load()
        entries[key] = record
        # merge-on-write: pick up entries other processes added meanwhile
        # (same-version files only: stale-format entries stay invalidated)
        on_disk: Dict[str, dict] = {}
        try:
            with open(self.path) as f:
                doc = json.load(f)
            if isinstance(doc, dict) and doc.get("version") == CACHE_VERSION:
                on_disk = _valid_entries(doc.get("entries"))
        except (OSError, ValueError):
            pass
        on_disk.update(entries)
        self._entries = on_disk
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"version": CACHE_VERSION, "entries": on_disk}, f,
                      indent=1, sort_keys=True)
        os.replace(tmp, self.path)


_DEFAULT_CACHE: Optional[AutotuneCache] = None


def default_cache() -> AutotuneCache:
    """Process-wide cache over :func:`default_cache_path` (re-resolved if
    the env var changed, so tests can repoint it)."""
    global _DEFAULT_CACHE
    path = default_cache_path()
    if _DEFAULT_CACHE is None or _DEFAULT_CACHE.path != path:
        _DEFAULT_CACHE = AutotuneCache(path)
    return _DEFAULT_CACHE


# --------------------------------------------------------------------------
# candidate executors + measurement
# --------------------------------------------------------------------------

def _candidate_fn(pack: KernelBSR, name: str):
    """-> (jitted fn, data arg) executing this backend for ``pack``."""
    from repro.kernels.ops import bsr_linear  # local: ops imports exec_plan
    if name == "dense":
        w = jnp.asarray(dense_from_pack(pack))
        return jax.jit(lambda x, w_: x @ w_.T), w
    if name == "plan":
        plan = xp.plan_for_pack(pack)
        data = xp.pack_plan_data(plan, pack.data)
        return jax.jit(lambda x, d, _p=plan: xp.plan_linear(x, d, _p)), data
    if name == "masked":
        mp = masked_pack_from(pack)
        w = jnp.asarray(dense_from_pack(pack))
        mask = jnp.asarray(mp.tile_mask)
        tile = pack.tile
        return (jax.jit(lambda x, w_: masked_matmul(x, w_, mask, tile=tile)),
                w)
    if name == "plan_pallas":
        plan = xp.plan_for_pack(pack)
        data = xp.pack_plan_data(plan, pack.data)
        return (jax.jit(lambda x, d, _p=plan:
                        xp.plan_linear_pallas(x, d, _p)), data)
    if name in ("plan_q8", "plan_pallas_q8"):
        # quantize the measurement data exactly like export would: the
        # timed op consumes int8 values + fp32 scales, dequant fused
        plan = xp.plan_for_pack(pack)
        data_rp = xp.pack_plan_data(plan, pack.data)
        q, s = xp.quantize_plan_values(
            data_rp, "int8", xp.quant_granularity(pack.tile))
        if name == "plan_q8":
            return (jax.jit(lambda x, d, _s=s, _p=plan:
                            xp.plan_q_linear(x, d, _s, _p)), q)
        return (jax.jit(lambda x, d, _s=s, _p=plan:
                        xp.plan_q_linear_pallas(x, d, _s, _p)), q)
    if name in ("gather", "rowpack", "pallas"):
        return (jax.jit(lambda x, d, _pk=pack, _b=name:
                        bsr_linear(x, d, _pk, _b)), pack.data)
    raise ValueError(f"unknown autotune candidate {name!r}")


def measure(pack: KernelBSR, m: int, candidates: Sequence[str], *,
            reps: int = 5, timer: Optional[Callable] = None
            ) -> Tuple[Dict[str, float], Dict[str, float]]:
    """Paired wall-clock micro-benchmark: interleave the reps of all
    candidates round-robin (machine drift hits every arm equally, the
    kernel_bench discipline). Returns ``(times, scores)``:

      * ``times`` -- min-of-reps seconds per candidate (reporting);
      * ``scores`` -- the RANKING statistic: per round, each arm's time is
        divided by the round's first-candidate time (arms in one round see
        the same machine state), and the median of those paired ratios is
        taken. On a shared box whose speed drifts between rounds this
        orders near-ties far more reliably than comparing each arm's
        luckiest absolute rep.

    ``timer(name, fn, args)`` substitutes the measurement -- the
    frozen-clock hook for tests (scores == times there)."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(m, pack.shape[1]).astype(np.float32))
    arms = [(name,) + _candidate_fn(pack, name) for name in candidates]
    if timer is not None:
        times = {name: float(timer(name, fn, (x, data)))
                 for name, fn, data in arms}
        return times, dict(times)
    for _, fn, data in arms:
        jax.block_until_ready(fn(x, data))          # compile + warm
    ts: Dict[str, list] = {name: [] for name, _, _ in arms}
    for _ in range(reps):
        for name, fn, data in arms:
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x, data))
            ts[name].append(time.perf_counter() - t0)
    anchor = np.asarray(ts[arms[0][0]], np.float64)
    scores = {name: float(np.median(np.asarray(t, np.float64) / anchor))
              for name, t in ts.items()}
    return {name: float(np.min(t)) for name, t in ts.items()}, scores


def stub_costs(pack: KernelBSR, m: int,
               candidates: Sequence[str]) -> Dict[str, float]:
    """Deterministic FLOP/traffic proxy (pseudo-seconds) used instead of
    wall clocks in stub mode. Not calibrated -- its only contracts are
    determinism and roughly-roofline-shaped ordering (dense wins dense-ish
    cells, the sparse paths win only when density actually pays, interpret
    mode never wins off-TPU)."""
    n, k = pack.shape
    bn, bk = pack.tile
    nnzt = pack.real_nnzt
    rows = np.asarray(pack.row_id[: nnzt], np.int64)
    counts = np.bincount(rows, minlength=pack.n_brows)
    p_max = max(1, int(counts.max()))
    plan = xp.plan_for_pack(pack)
    on_tpu = jax.default_backend() == "tpu"
    interp = 0.0 if on_tpu else 1e6 * nnzt          # interpret-mode penalty
    traffic = 8.0                                   # weight-stream weight
    out = {}
    for name in candidates:
        if name == "dense":
            c = m * n * k + traffic * n * k
        elif name == "gather":
            c = 2.5 * m * nnzt * bn * bk + traffic * nnzt * bn * bk
        elif name == "rowpack":
            # per-call scatter of every stored tile + padded batched matmul
            c = (4 * traffic * nnzt * bn * bk
                 + m * pack.n_brows * p_max * bn * bk)
        elif name == "plan":
            c = (m * plan.n_vrows * plan.p_max * bn * bk
                 + traffic * nnzt * bn * bk)
            if plan.spilled:
                c += m * plan.n_vrows * bn
        elif name == "pallas":
            c = m * nnzt * bn * bk + traffic * nnzt * bn * bk + interp
        elif name == "plan_pallas":
            # same real-tile FLOPs and weight stream as 'pallas', minus the
            # padded-slot work 'plan' pays, with spills + epilogue folded
            # into the row-change write -- a small scheduling edge that
            # breaks the tie toward the plan-consuming kernel on TPU
            c = (0.97 * m * nnzt * bn * bk + traffic * nnzt * bn * bk
                 + interp)
        elif name in ("plan_q8", "plan_pallas_q8"):
            # int8 values cut the weight stream 4x vs fp32, but add a
            # per-block (or per-row-group) fp32 scale stream; FLOPs match
            # the fp32 arm (dequant fuses into the accumulate)
            gran = xp.quant_granularity(pack.tile)
            scale_elems = plan.n_vrows * (plan.p_max if gran == "block"
                                          else 1)
            qtraffic = traffic * nnzt * bn * bk / 4.0 + traffic * scale_elems
            if name == "plan_q8":
                c = m * plan.n_vrows * plan.p_max * bn * bk + qtraffic
                if plan.spilled:
                    c += m * plan.n_vrows * bn
            else:
                c = 0.97 * m * nnzt * bn * bk + qtraffic + interp
        elif name == "masked":
            c = m * nnzt * bn * bk + traffic * n * k + interp
        else:
            raise ValueError(f"unknown autotune candidate {name!r}")
        out[name] = float(c)
    return out


# --------------------------------------------------------------------------
# the chooser
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Choice:
    backend: str
    costs: Dict[str, float]     # seconds (wallclock) or proxy (stub)
    cache_hit: bool
    mode: str                   # 'wallclock' | 'stub'
    key: str


def choose_backend(pack: KernelBSR, m: int = 256, *,
                   candidates: Optional[Sequence[str]] = None,
                   cache: Optional[AutotuneCache] = None,
                   stub: Optional[bool] = None, reps: int = 5,
                   timer: Optional[Callable] = None,
                   shard: Optional[Tuple[int, str]] = None,
                   quant: str = "none") -> Choice:
    """Pick the fastest execution path for ``pack`` on this device.

    Consults the on-disk winner cache first (one measurement per
    (pattern, shard, m, device kind, device count, mode, quant, value
    dtype) EVER, across processes); on a miss it measures (or, in stub
    mode, ranks by the deterministic proxy) and persists the winner.

    ``quant`` is the serving pack quantization ('none' | 'int8' | 'fp8').
    When set and ``candidates`` is None, the quantized arms
    (:data:`QUANT_CANDIDATES`) join the default set so 'auto' can pick
    between fp32 and quantized plans. It is always folded into the cache
    key -- alongside the value dtype -- so a winner measured for fp32
    packs never answers for quantized ones (and vice versa); entries
    written before this keying are simply never matched again.

    ``shard = (n_shards, axis)`` tags the key with the tensor-parallel
    partitioning AND the per-shard sub-problem shape, and the measurement
    itself runs on the per-shard sub-problem (:func:`shard_subpack`, the
    most occupied shard): an 8-way-sharded pack runs 8 per-device problems
    an 8th the size, so a winner measured unsharded (or at a different
    shard count) is neither keyed nor measured for it.
    """
    stub = stub_mode() if stub is None else bool(stub)
    cache = cache if cache is not None else default_cache()
    if candidates is None:
        candidates = list(CANDIDATES)
        if quant != "none":
            candidates += list(QUANT_CANDIDATES)
        if (not stub and timer is None and jax.default_backend() != "tpu"
                or not pallas_interpret_default()
                and not pallas_tile_supported(pack.tile)):
            # off TPU the Pallas arms only interpret; on TPU they cannot
            # compile tiles off the (8, 128) tiling
            candidates = [c for c in candidates if c not in INTERPRET_ONLY]
    mode = "stub" if stub else "wallclock"
    # the candidate set is part of the key: a winner measured over a
    # narrow set must not answer for a broader one (the extra backends
    # were never measured)
    cand_tag = hashlib.sha1(
        ",".join(sorted(candidates)).encode()).hexdigest()[:8]
    shard_tag = ""
    measure_pack = pack
    if shard is not None and int(shard[0]) > 1:
        from repro.kernels.exec_plan import shard_divisible
        n_shards, axis = int(shard[0]), shard[1]
        if not shard_divisible(pack, n_shards, axis):
            # an indivisible pattern serves through the replicated
            # fallback, i.e. unsharded -- key and measure it as such
            # (serving/export guards this too; this covers direct callers)
            n_shards = 0
        else:
            n, k = pack.shape
            sn = n // n_shards if axis == "out" else n
            sk = k // n_shards if axis == "in" else k
            shard_tag = f":s{axis}{n_shards}x{sn}x{sk}"
            # measure the per-device problem, not the full matrix: under
            # TP each device runs a 1/n_shards-sized matmul, whose winner
            # can differ (smaller problems lean dense)
            measure_pack = shard_subpack(pack, n_shards, axis)
    key = (f"{pattern_digest(pack)}:m{int(m)}:{device_kind()}"
           f":d{jax.device_count()}{shard_tag}:{mode}"
           f":q{quant}:w{np.dtype(pack.data.dtype).name}:c{cand_tag}")
    rec = cache.get(key)
    if rec is not None and rec.get("backend") in candidates:
        return Choice(rec["backend"], dict(rec.get("costs", {})), True,
                      mode, key)
    if stub:
        costs = stub_costs(measure_pack, m, candidates)
        scores = costs
    else:
        costs, scores = measure(measure_pack, m, candidates, reps=reps,
                                timer=timer)
    backend = min(scores, key=scores.get)
    cache.put(key, {"backend": backend, "costs": costs, "mode": mode,
                    "m": int(m), "device": device_kind(),
                    "devices": jax.device_count(),
                    "shard": shard_tag.lstrip(":") or None,
                    "quant": quant,
                    "created": time.strftime("%Y-%m-%dT%H:%M:%S")})
    return Choice(backend, costs, False, mode, key)


# --------------------------------------------------------------------------
# decode-kernel selection (attention decode step: 'xla' vs split-K 'flash')
# --------------------------------------------------------------------------

def decode_stub_costs(*, b: int, t: int, hq: int, hkv: int, d: int,
                      kv_split: int) -> Dict[str, float]:
    """Deterministic proxy for the one-token decode step (pseudo-seconds).

    Both arms stream the full KV cache once (the roofline floor). On top of
    that, 'xla' pays the materialized (B, Hq, T) scores + probs HBM
    round-trip; 'flash' pays the split-K reduce: per split, the on-chip
    (m, l, acc) running state -- (G, d + 2) floats per (slot, kv head) --
    is corrected and re-written, so cost grows with ``kv_split`` while the
    score tensor never touches HBM. Off-TPU the interpret penalty keeps
    'flash' from ever winning (same contract as INTERPRET_ONLY)."""
    g = max(1, hq // hkv)
    on_tpu = jax.default_backend() == "tpu"
    interp = 0.0 if on_tpu else 1e6 * t
    traffic = 8.0
    flops = 2.0 * b * hq * t * d
    kv_read = traffic * b * hkv * t * d
    return {
        "xla": flops + kv_read + traffic * 2.0 * b * hq * t,
        "flash": (flops + kv_read
                  + traffic * b * hkv * g * (d + 2) * kv_split + interp),
    }


def _measure_decode(b, t, hq, hkv, d, window, kv_split, candidates, *,
                    reps=3, timer=None):
    """Paired wall-clock micro-benchmark of the decode arms (same
    round-robin + paired-ratio discipline as :func:`measure`)."""
    from repro.kernels.flash_decode import flash_decode
    from repro.models.attention import decode_attention
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, 1, hq, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, hkv, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, hkv, d).astype(np.float32))
    pm = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    pos = jnp.full((b,), t - 1, jnp.int32)
    fns = {
        "xla": jax.jit(lambda q, k, v, pm, pos: decode_attention(
            q, k, v, pm, pos, window=window)),
        "flash": jax.jit(lambda q, k, v, pm, pos: flash_decode(
            q, k, v, pm, pos, window=window, kv_split=kv_split)),
    }
    arms = [(name, fns[name]) for name in candidates]
    if timer is not None:
        times = {name: float(timer(name, fn, (q, k, v, pm, pos)))
                 for name, fn in arms}
        return times, dict(times)
    for _, fn in arms:
        jax.block_until_ready(fn(q, k, v, pm, pos))
    ts: Dict[str, list] = {name: [] for name, _ in arms}
    for _ in range(reps):
        for name, fn in arms:
            t0 = time.perf_counter()
            jax.block_until_ready(fn(q, k, v, pm, pos))
            ts[name].append(time.perf_counter() - t0)
    anchor = np.asarray(ts[arms[0][0]], np.float64)
    scores = {name: float(np.median(np.asarray(v_, np.float64) / anchor))
              for name, v_ in ts.items()}
    return {name: float(np.min(v_)) for name, v_ in ts.items()}, scores


def choose_decode_kernel(b: int = 8, t: int = 512, hq: int = 8,
                         hkv: int = 8, d: int = 64, *, window: int = 0,
                         kv_split: Optional[int] = None,
                         cache: Optional[AutotuneCache] = None,
                         stub: Optional[bool] = None, reps: int = 3,
                         timer: Optional[Callable] = None) -> Choice:
    """Pick the attention decode kernel ('xla' | 'flash') for this shape on
    this device, with the same cache / stub / frozen-timer contract as
    :func:`choose_backend`. ``Servable`` consults this when
    ``spec.decode_kernel='auto'`` and no env override pins the choice."""
    if hq < 1 or hkv < 1 or d < 1:
        raise ValueError(f"attention-free decode shape (hq={hq}, hkv={hkv}, "
                         f"d={d}); pin decode kernel instead of tuning")
    stub = stub_mode() if stub is None else bool(stub)
    cache = cache if cache is not None else default_cache()
    split = int(kv_split) if kv_split else default_kv_split(t)
    candidates = list(DECODE_CANDIDATES)
    if (not stub and timer is None and jax.default_backend() != "tpu"
            or not pallas_interpret_default()
            and not flash_decode_aligned(hkv, d)):
        candidates = [c for c in candidates
                      if c not in DECODE_INTERPRET_ONLY]
    mode = "stub" if stub else "wallclock"
    cand_tag = hashlib.sha1(
        ",".join(sorted(candidates)).encode()).hexdigest()[:8]
    key = (f"decode:b{int(b)}t{int(t)}h{int(hq)}g{int(hkv)}d{int(d)}"
           f"w{int(window)}s{split}:{device_kind()}"
           f":d{jax.device_count()}:{mode}:c{cand_tag}")
    rec = cache.get(key)
    if rec is not None and rec.get("backend") in candidates:
        return Choice(rec["backend"], dict(rec.get("costs", {})), True,
                      mode, key)
    if stub:
        all_costs = decode_stub_costs(b=b, t=t, hq=hq, hkv=hkv, d=d,
                                      kv_split=split)
        costs = {name: all_costs[name] for name in candidates}
        scores = costs
    else:
        costs, scores = _measure_decode(b, t, hq, hkv, d, window, split,
                                        candidates, reps=reps, timer=timer)
    backend = min(scores, key=scores.get)
    cache.put(key, {"backend": backend, "costs": costs, "mode": mode,
                    "device": device_kind(),
                    "devices": jax.device_count(),
                    "created": time.strftime("%Y-%m-%dT%H:%M:%S")})
    return Choice(backend, costs, False, mode, key)
