"""Required work (operations and bytes) of the served computations.

Counted from the configuration's shapes, the number of nonzero tiles of
each pruned projection (known from the masks the benchmark itself drew) and
the served dtype. Never from a kernel's padded grid or from compiled HLO:
a roofline share then reads the same work whatever implements it, and a
change that pads or recomputes shows as a lower share.

Each function returns ``(flops, bytes)``; ``least_s`` turns that into the
least time the chip could take, the larger of the two roofline bounds.
"""
from __future__ import annotations

from typing import Tuple

Work = Tuple[float, float]


def least_s(work: Work, peak_flops: float, peak_bw: float) -> float:
    flops, nbytes = work
    return max(flops / peak_flops, nbytes / peak_bw)


def add(*works: Work) -> Work:
    return (sum(w[0] for w in works), sum(w[1] for w in works))


def packed_matmul(m: int, k: int, n: int, nnz_tiles: int, tile,
                  wbytes: int = 2, abytes: int = 2) -> Work:
    """Y (m, n) = X (m, k) @ W^T with W block-sparse: only the ``nnz_tiles``
    stored tiles are multiplied and read; X is read and Y written once."""
    bn, bk = tile
    flops = 2.0 * m * nnz_tiles * bn * bk
    nbytes = nnz_tiles * bn * bk * wbytes + m * (k + n) * abytes
    return flops, float(nbytes)


def dense_matmul(m: int, k: int, n: int, wbytes: int = 2,
                 abytes: int = 2, obytes: int = 2) -> Work:
    return 2.0 * m * k * n, float(k * n * wbytes + m * k * abytes
                                  + m * n * obytes)


def attention(q_len: int, kv_len: int, n_heads: int, head_dim: int,
              n_kv_heads: int, causal: bool, abytes: int = 2) -> Work:
    """Scores and weighted sum of one sequence: 4 * H * D per (query, key)
    pair that the mask admits; Q, K, V read and the output written once."""
    if causal:   # query i of a prompt sees keys 0..kv_len - q_len + i
        pairs = q_len * (kv_len - q_len) + q_len * (q_len + 1) / 2
    else:
        pairs = q_len * kv_len
    flops = 4.0 * n_heads * head_dim * pairs
    nbytes = (2 * q_len * n_heads * head_dim
              + 2 * kv_len * n_kv_heads * head_dim) * abytes
    return flops, float(nbytes)


def decode_attention(ctx: int, n_heads: int, head_dim: int, n_kv_heads: int,
                     kv_bytes: int = 2) -> Work:
    """One new query against ``ctx`` cached positions: the K and V of those
    positions are read once (the padding up to the cache length is not
    required work)."""
    return (4.0 * n_heads * head_dim * ctx,
            float(2 * ctx * n_kv_heads * head_dim * kv_bytes))
