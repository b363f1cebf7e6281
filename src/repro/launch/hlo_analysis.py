"""Roofline terms from compiled artifacts (no real hardware needed).

  compute     = HLO_FLOPs / (chips * peak)          [cost_analysis]
  memory      = HLO_bytes / (chips * hbm_bw)        [cost_analysis]
  collective  = sum(output bytes of all-gather/all-reduce/reduce-scatter/
                all-to-all/collective-permute) / (chips * link_bw)
                [parsed from compiled HLO text]

Conventions: cost_analysis flops/bytes on an SPMD module are per-partition
in recent jax (we multiply back to fleet totals where needed -- the ratios
reported divide out); collective volume counts each op's *output* tensor
bytes once per op (documented approximation; ring-algorithm factors (P-1)/P
are ~1 at P=256).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict

#: Published per-chip peaks keyed by ``jax.Device.device_kind``. Source:
#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
#: 1,600 Gbit/s chip-to-chip interconnect (200 GB/s over 4 links).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12,       # bf16 / chip
                    "hbm_bw": 819e9,       # bytes/s / chip
                    "link_bw": 50e9},      # bytes/s / link
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The :data:`PEAKS` row for ``device_kind``; a kind without published
    peaks is an error, never a silent default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.:  %all-gather.5 = bf16[16,1024,512]{2,1,0} all-gather(...)
_OP_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^\s]*\s+(" +
    "|".join(_COLLECTIVES) + r")[\.( ]")
# tuple-result collectives:  = (f32[8,4]{...}, f32[8,4]{...}) all-reduce(
_TUPLE_RE = re.compile(
    r"=\s*\(([^)]+)\)\s+(" + "|".join(_COLLECTIVES) + r")[\.( ]")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind output bytes summed over the module."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if m:
            dtype, dims, kind = m.groups()
            out[kind] += _shape_bytes(dtype, dims)
            continue
        m = _TUPLE_RE.search(line)
        if m:
            parts, kind = m.groups()
            for sm in _SHAPE_RE.finditer(parts):
                out[kind] += _shape_bytes(*sm.groups())
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclasses.dataclass
class Roofline:
    flops: float                 # per-device HLO flops
    hbm_bytes: float             # per-device HLO bytes accessed
    coll_bytes: float            # per-device collective bytes
    n_devices: int
    model_flops: float = 0.0     # 6*N*D-style useful flops (fleet-wide)
    device_kind: str = dataclasses.field(kw_only=True)  # a PEAKS key

    def __post_init__(self):
        self.peak = peaks(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops / self.peak["flops"]

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / self.peak["hbm_bw"]

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / self.peak["link_bw"]

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flop_ratio(self) -> float:
        total = self.flops * self.n_devices
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of peak sustained if the dominant term were the wall:
        useful_flops / (chips * peak * t_dominant)."""
        t = max(self.t_compute, self.t_memory, self.t_collective)
        if t <= 0:
            return 0.0
        return self.model_flops / (self.n_devices * self.peak["flops"] * t)

    def to_dict(self) -> dict:
        return {
            "flops_per_dev": self.flops, "hbm_bytes_per_dev": self.hbm_bytes,
            "coll_bytes_per_dev": self.coll_bytes, "n_devices": self.n_devices,
            "device_kind": self.device_kind,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective, "bottleneck": self.bottleneck,
            "useful_flop_ratio": self.useful_flop_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def count_params(shape_tree) -> int:
    import jax
    return sum(int(l.size) for l in jax.tree_util.tree_leaves(shape_tree)
               if hasattr(l, "size"))


def count_expert_params(shape_tree) -> int:
    """Routed-expert weights only. Expert leaves are raw (E, a, b) arrays
    named .../ffn/{wi,wg,wo}; DENSE mlp weights live one level deeper
    (.../ffn/wi/w) and must NOT be counted even when scan-stacking makes
    them 3-D."""
    import jax
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shape_tree)[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        if ("ffn" in name and not name.endswith("/w")
                and getattr(leaf, "ndim", 0) >= 3 and "shared" not in name):
            total += int(leaf.size)
    return total


def model_flops_estimate(cfg, shape, n_params: int, n_expert_params: int,
                         kind: str) -> float:
    """6*N_active*D for train, 2*N_active*D for inference-prefill,
    2*N_active*B per decoded token."""
    active = n_params - n_expert_params
    if cfg.n_experts:
        active += n_expert_params * (cfg.top_k + cfg.n_shared_experts) / cfg.n_experts
    # embedding rows aren't multiplied per token; subtract one embed table
    active -= cfg.vocab_size * cfg.d_model
    tokens = shape.global_batch * (1 if kind == "decode" else shape.seq_len)
    mult = 6.0 if kind == "train" else 2.0
    return mult * active * tokens
