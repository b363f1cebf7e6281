"""The one traffic generator: reads a mix's parameters and draws its
requests from ``--seed``.

Lengths are stratified: a pool of ``pool`` requests takes the quantiles of
its length distribution once each, and the seed only shuffles them and
draws the token ids. Every seed therefore offers the same set of sizes in
another order, so two seeds differ about as little as two runs of one seed.
A closed loop that outlasts the pool takes the next shuffle of it.
"""
from __future__ import annotations

import math
from typing import Dict, Iterator, Tuple

import numpy as np

SUPPORTED = ("loguniform", "fixed")


def stratified(spec: Dict, pool: int) -> np.ndarray:
    """``pool`` lengths at the mid-quantiles of ``spec``'s distribution:
    ``{"dist": "loguniform", "min": a, "max": b}`` or ``{"dist": "fixed",
    "value": v}``."""
    dist = spec.get("dist", "fixed")
    if dist == "fixed":
        return np.full((pool,), int(spec["value"]), np.int64)
    if dist != "loguniform":
        raise ValueError(f"length distribution {dist!r} not in {SUPPORTED}")
    lo, hi = math.log(spec["min"]), math.log(spec["max"])
    q = (np.arange(pool) + 0.5) / pool
    return np.clip(np.rint(np.exp(lo + q * (hi - lo))), spec["min"],
                   spec["max"]).astype(np.int64)


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy generator per (seed, stream); any whole seed."""
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), stream])


def jax_seed(seed: int) -> int:
    """A 31-bit key seed for ``jax.random`` derived from any whole seed."""
    return int(rng(seed, 99).integers(0, 2 ** 31 - 1))


def token_batches(mix: Dict, seed: int, vocab: int) -> np.ndarray:
    """Encoder traffic: ``pool`` distinct batches of ``batch`` x ``seq``
    token ids, (pool, batch, seq) int32."""
    g = rng(seed, 1)
    return g.integers(0, vocab, (mix["pool"], mix["batch"], mix["seq"]),
                      dtype=np.int32)


def request_stream(mix: Dict, seed: int, vocab: int
                   ) -> Iterator[Tuple[np.ndarray, int]]:
    """Decoder traffic: an endless stream of (prompt token ids, output
    tokens), one shuffled pool after another."""
    pool = int(mix["pool"])
    prompts = stratified(mix["prompt"], pool)
    outputs = stratified(mix["output"], pool)
    cycle = 0
    while True:
        g = rng(seed, 10 + cycle)
        p_order, o_order = g.permutation(pool), g.permutation(pool)
        for i in range(pool):
            n = int(prompts[p_order[i]])
            yield (g.integers(0, vocab, (n,), dtype=np.int32),
                   int(outputs[o_order[i]]))
        cycle += 1
