"""Shared pieces of the plain references: the benchmark's own weights and
straightforward float32 layer math. Nothing here imports the program.

Weights are drawn from the seed in the served dtype, with tied block masks
(one mask per projection, shared by every layer: the paper's tied pruning,
so the cross-layer union adds no padding). The masks come from the
configuration's fixed ``serving.mask_seed``, not from the run's seed: every
run serves the same pattern, so the kernels specialized to it compile once
and come from the persistent compile cache afterwards. Each layer's weights
come from its own key, so a reference can regenerate one layer at a time
and get the values the program was given, bit for bit.

``quant="fp8"`` turns every projection into the W8A8 scheme a serving
stack would reach for below bfloat16: float8 (e4m3) weights with one scale
per output channel, float8 activations with one scale per row, products
summed in float32. That is the control that the correctness limits must
reject.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

INIT_STD = 0.02       # the program's own initializer range
NORM_STD = 0.1        # norm scales and biases are drawn too, so they matter


def keep_tiles(shape: Tuple[int, int], tile, sparsity: float) -> int:
    """Tiles that survive pruning a ``shape`` weight at ``sparsity``."""
    nb = (shape[0] // tile[0]) * (shape[1] // tile[1])
    return max(1, int(round(nb * (1.0 - sparsity))))


def mask_key(conf):
    """The configuration's fixed key for its pruning masks."""
    return jax.random.PRNGKey(int(conf["serving"]["mask_seed"]))


def draw_mask(key, shape, tile, sparsity: float):
    """A block mask keeping exactly ``keep_tiles`` tiles, chosen uniformly
    from ``key``; expanded to the elementwise (N, K) {0, 1} mask."""
    nbr, nbc = shape[0] // tile[0], shape[1] // tile[1]
    k = keep_tiles(shape, tile, sparsity)
    order = jnp.argsort(jax.random.uniform(key, (nbr * nbc,)))
    blocks = jnp.zeros((nbr * nbc,), jnp.float32).at[order[:k]].set(1.0)
    blocks = blocks.reshape(nbr, nbc)
    return jnp.repeat(jnp.repeat(blocks, tile[0], 0), tile[1], 1)


def weight(key, shape, dtype, mask=None):
    w = INIT_STD * jax.random.normal(key, shape, jnp.float32)
    if mask is not None:
        w = w * mask
    return w.astype(dtype)


def norm_params(key, d, dtype, bias: bool):
    ks, kb = jax.random.split(key)
    out = {"scale": (NORM_STD * jax.random.normal(ks, (d,))).astype(dtype)}
    if bias:
        out["bias"] = (NORM_STD * jax.random.normal(kb, (d,))).astype(dtype)
    return out


def f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


# -- float32 layer math -------------------------------------------------------

FP8_MAX = 448.0       # largest finite float8_e4m3fn


def _fp8(x, axis):
    """Scale each row to the float8_e4m3fn range, round to it, and return
    the rounded values (as float32) with their scales."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32), s


def linear(x, w, quant: Optional[str] = None):
    """x (..., K) @ w (N, K)^T in float32 at full precision; ``"fp8"``
    rounds both operands to float8 first (W8A8, see the module docstring)."""
    if quant is None:
        return jnp.einsum("...k,nk->...n", x, w,
                          precision=jax.lax.Precision.HIGHEST)
    assert quant == "fp8", quant
    wq, ws = _fp8(w, axis=1)                    # (N, K), (N, 1)
    xq, xs = _fp8(x, axis=-1)                   # (..., K), (..., 1)
    y = jnp.einsum("...k,nk->...n", xq, wq,
                   precision=jax.lax.Precision.HIGHEST)
    return y * xs * ws[:, 0]


def layer_norm(x, p, eps: float):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def rms_norm(x, p, eps: float):
    """Llama RMSNorm with the weight stored as an offset from 1 (the
    program's parameterization of the same function)."""
    var = jnp.mean(x * x, -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + p["scale"])


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                     * (x + 0.044715 * x ** 3)))


def rope(x, positions, theta: float):
    """Rotary embedding, rotate-half convention (HF Llama): x (S, H, D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * inv          # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, causal: bool, q_block: int = 512):
    """softmax(q k^T / sqrt(D)) v for one sequence, q (S, H, D), k/v
    (T, H, D); queries in blocks so that long prompts fit."""
    s, h, d = q.shape
    outs = []
    for a in range(0, s, q_block):
        qb = q[a:a + q_block]
        sc = jnp.einsum("shd,thd->hst", qb, k,
                        precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(
                            jnp.float32(d))
        if causal:
            qpos = jnp.arange(a, a + qb.shape[0])[:, None]
            sc = jnp.where(jnp.arange(k.shape[0])[None] <= qpos, sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hst,thd->shd", p, v,
                               precision=jax.lax.Precision.HIGHEST))
    return jnp.concatenate(outs, 0)


def stack_map(fn, keys: Sequence):
    """``fn`` over each key in order, leaves stacked on a leading axis
    (``lax.map``: one layer's temporaries live at a time)."""
    return jax.lax.map(fn, jnp.stack(list(keys)))


def tree_index(tree: Dict, i: int):
    return jax.tree_util.tree_map(lambda x: x[i], tree)
