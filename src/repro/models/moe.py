"""Mixture-of-Experts FFN over the experts this device holds, drop-free.

The router keeps its full width: float32 logits over all ``n_experts``, a
softmax, the top ``top_k`` experts of each token, their weights
renormalized only under ``norm_topk_prob`` and multiplied by
``routed_scaling_factor``. The device holds experts ``expert_offset`` ..
``expert_offset + n_held - 1`` (all of them in training) and computes their
part of the layer alone: assignments to other experts are left out of its
sum, as expert parallelism leaves them to the devices that hold them (the
exchange between devices is not part of this layer). Held assignments are
sorted into per-expert groups and run through one grouped SwiGLU matmul
(``kernels/moe_gmm.py``); shared experts are added once.

No capacity: every assignment is computed, so a token's output does not
depend on the other tokens in its batch. Switch-style aux loss for
training.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm import group_layout, moe_gmm, row_tile
from repro.models.common import apply_mlp, init_mlp, normal_init


def init_moe(key, cfg):
    d, f, h = cfg.d_model, cfg.d_ff_expert or cfg.d_ff, cfg.n_held
    ks = jax.random.split(key, 5)
    p = {"router": normal_init(ks[0], (d, cfg.n_experts), 0.02, jnp.float32),
         "wi": normal_init(ks[1], (h, d, f), 0.02, cfg.jdtype),
         "wg": normal_init(ks[2], (h, d, f), 0.02, cfg.jdtype),
         "wo": normal_init(ks[3], (h, f, d), 0.02, cfg.jdtype)}
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], d, f * cfg.n_shared_experts,
                               cfg.act, cfg.jdtype)
    return p


def route(router, x2, cfg):
    """(probs (T, E), gate (T, k), expert ids (T, k)) of tokens x2 (T, d)."""
    logits = jnp.einsum("td,de->te", x2.astype(jnp.float32), router,
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return probs, gate * cfg.routed_scaling_factor, idx


def apply_moe(p, x, cfg, *, valid=None, packs=None, layer=None):
    """x: (B, S, d) -> (y, aux_loss, rows): ``rows`` (n_held,) int32 counts
    the assignments each held expert computed. ``valid`` (B, S) bool marks
    real tokens: padding and inactive decode slots route nowhere. ``packs``
    serve the shared experts' projections (``shared/<proj>``). With
    ``layer``, the routed experts ``wi``/``wg``/``wo`` are stacked over the
    layer scan's layers and read at that index (``moe_gmm``)."""
    b, s, d = x.shape
    t, k, held = b * s, cfg.top_k, cfg.n_held
    x2 = x.reshape(t, d)
    probs, gate, idx = route(p["router"], x2, cfg)

    local = idx - cfg.expert_offset
    mine = (local >= 0) & (local < held)
    if valid is not None:
        mine &= valid.reshape(t, 1)
    groups = jnp.where(mine, local, held).reshape(-1).astype(jnp.int32)
    tm = row_tile(t * k / cfg.n_experts)
    lay = group_layout(groups, held, tm, t * min(k, held))
    rows_x = jnp.take(x2, lay.row_src // k, axis=0, mode="fill",
                      fill_value=0)
    out = moe_gmm(rows_x, p["wi"], p["wg"], p["wo"], lay,       # (R, d) f32
                  layer=layer)
    routed = jnp.take(out, lay.dest, axis=0, mode="fill",
                      fill_value=0).reshape(t, k, d) * gate[..., None]
    y = routed[:, 0]
    for j in range(1, k):               # a fixed order, whatever the batch
        y = y + routed[:, j]
    rows = jnp.zeros((held + 1,), jnp.int32).at[groups].add(1)[:held]

    if "shared" in p:
        sp = None if packs is None else {
            n[len("shared/"):]: v for n, v in packs.items()
            if n.startswith("shared/")} or None
        y = y + apply_mlp(p["shared"], x2, cfg.act,
                          packs=sp).astype(jnp.float32)

    # Switch-style aux loss: E * sum(frac_tokens_e * mean_prob_e)
    e = cfg.n_experts
    frac = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * mean_prob) * cfg.router_aux_coef
    return y.reshape(b, s, d).astype(x.dtype), aux, rows
