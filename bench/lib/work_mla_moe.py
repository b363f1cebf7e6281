"""Required work (operations and bytes) of latent attention and of the
experts a device holds, counted from the configuration's shapes and the
served dtype, as ``lib/work.py`` counts the rest: never from a kernel's
grid.

Each function returns ``(flops, bytes)``.
"""
from __future__ import annotations

from lib.work import Work


def mla_decode(ctx: int, n_heads: int, rank: int, rope_dim: int,
               kv_bytes: int = 2) -> Work:
    """One query against ``ctx`` cached latents, absorbed: per head, scores
    over the rank-``rank`` latent and the rope key, and the weighted sum of
    the latents; the cache of those positions (latent and rope key) is
    read once."""
    return (2.0 * n_heads * ctx * (2 * rank + rope_dim),
            float(ctx * (rank + rope_dim) * kv_bytes))


def mla_prefill(p: int, n_heads: int, qk_dim: int, v_dim: int,
                abytes: int = 2) -> Work:
    """Causal attention of a ``p``-token prompt over its expanded keys
    (``qk_dim`` per head) and values (``v_dim``); Q, K, V read and the
    output written once."""
    pairs = p * (p + 1) / 2
    return (2.0 * n_heads * pairs * (qk_dim + v_dim),
            float(p * n_heads * (2 * qk_dim + 2 * v_dim) * abytes))


def moe_gmm(tokens: int, top_k: int, held: int, n_experts: int, d: int,
            f: int, wbytes: int = 2, abytes: int = 2) -> Work:
    """The held experts' SwiGLU over the rows routed to them: ``tokens`` x
    ``top_k`` x ``held`` / ``n_experts`` rows on average; every held
    expert's three (d, f) weights read once; rows read in ``abytes`` and
    written in float32."""
    rows = tokens * top_k * held / n_experts
    return (6.0 * rows * d * f,
            float(3 * held * d * f * wbytes + rows * d * (abytes + 4)))
