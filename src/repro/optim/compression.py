"""Block-sparse gradient compression with error feedback (DP all-reduce path).

Distributed-optimization trick tied to the paper's theme: gradients are
compressed to the top-K *blocks* per tensor (the same block-magnitude
machinery as core.sparsity) before the data-parallel exchange; the residual
accumulates in an error-feedback buffer (Deep-Gradient-Compression style) so
convergence is preserved.

Wire format per tensor: (values (K, bh, bw), flat block indices (K,)). The
collective becomes an all-gather of K*bh*bw + K elements per peer instead of
an all-reduce of the full tensor -- at 1-5 % density this is a >10x byte
reduction on the DP axis, visible in the dry-run HLO as all-gathers of small
operands. Used inside shard_map over the DP axes (launch/train.py, flag
``grad_compression``); FSDP-sharded dims stay uncompressed (scope note in
DESIGN.md).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    block_shape: Tuple[int, int] = (8, 128)   # lane-aligned wire blocks
    density: float = 0.05                     # fraction of blocks kept
    min_size: int = 65536                     # don't compress small leaves


def _blockify(g, bs):
    bh, bw = bs
    r, c = g.shape
    return g.reshape(r // bh, bh, c // bw, bw).transpose(0, 2, 1, 3).reshape(
        -1, bh, bw)


def _unblockify(blocks, shape, bs):
    bh, bw = bs
    r, c = shape
    return blocks.reshape(r // bh, c // bw, bh, bw).transpose(0, 2, 1, 3
                                                              ).reshape(r, c)


def compressible(leaf, cfg: CompressionConfig) -> bool:
    bh, bw = cfg.block_shape
    return (leaf.ndim == 2 and leaf.size >= cfg.min_size
            and leaf.shape[0] % bh == 0 and leaf.shape[1] % bw == 0)


def compress(g, err, cfg: CompressionConfig):
    """(grad, error buffer) -> (values, indices, new_error)."""
    acc = g.astype(jnp.float32) + err
    blocks = _blockify(acc, cfg.block_shape)              # (NB, bh, bw)
    nb = blocks.shape[0]
    k = max(1, int(nb * cfg.density))
    norms = jnp.sum(blocks * blocks, axis=(1, 2))
    _, idx = jax.lax.top_k(norms, k)                      # (K,)
    vals = blocks[idx]                                    # (K, bh, bw)
    kept = jnp.zeros((nb,), bool).at[idx].set(True)
    new_err = _unblockify(jnp.where(kept[:, None, None], 0.0, blocks),
                          acc.shape, cfg.block_shape)
    return vals, idx.astype(jnp.int32), new_err


def decompress(vals, idx, shape, cfg: CompressionConfig):
    bh, bw = cfg.block_shape
    nb = (shape[0] // bh) * (shape[1] // bw)
    blocks = jnp.zeros((nb, bh, bw), jnp.float32).at[idx].add(vals)
    return _unblockify(blocks, shape, cfg.block_shape)


def compressed_allreduce(g, err, cfg: CompressionConfig, axis_names):
    """Inside shard_map: mean-reduce ``g`` over ``axis_names`` at reduced
    traffic. Returns (reduced_grad, new_error)."""
    vals, idx, new_err = compress(g, err, cfg)
    gv = jax.lax.all_gather(vals, axis_names, tiled=False)   # (P, K, bh, bw)
    gi = jax.lax.all_gather(idx, axis_names, tiled=False)    # (P, K)
    n_peers = gv.shape[0]
    summed = decompress(gv.reshape(-1, *vals.shape[1:]),
                        gi.reshape(-1), g.shape, cfg)
    return (summed / n_peers).astype(g.dtype), new_err


def make_compressed_sync(mesh, axis_names, cfg: CompressionConfig):
    """Build a shard_map'd (grad, err) -> (mean_grad, new_err) sync for one
    2-D tensor. check_vma=False: gradients are device-VARYING despite the
    replicated-shape specs (classic DP semantics)."""
    from functools import partial
    from jax.sharding import PartitionSpec as P

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
             out_specs=(P(), P()), check_vma=False)
    def sync(g, e):
        return compressed_allreduce(g, e, cfg, axis_names)

    return sync


def init_error_buffers(params, cfg: CompressionConfig):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32) if compressible(p, cfg)
        else jnp.zeros((1,), jnp.float32), params)
