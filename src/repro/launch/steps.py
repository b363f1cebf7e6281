"""Step builders: train_step (CE + aux + group-ℓ1, AdamW, prox) and
serve_step / prefill_step. Pure functions suitable for jax.jit AOT lowering.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.regularizer import tree_group_penalty
from repro.models import decode_step as model_decode_step
from repro.models import model_forward
from repro.optim.adamw import AdamWConfig, adamw_update


def cross_entropy(logits, labels):
    """logits (B,S,V) f32, labels (B,S) int32; mean over all tokens."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def make_loss_fn(cfg: ModelConfig, packs=None):
    def loss_fn(params, batch):
        logits, aux = model_forward(params, cfg, batch, packs=packs)
        ce = cross_entropy(logits, batch["labels"])
        loss = ce + aux
        if cfg.sparsity is not None and cfg.sparsity.lambda_reg > 0:
            # proximal-gradient: the group-lasso term is handled EXACTLY by
            # the blockwise soft-threshold in the optimizer (adamw_update);
            # the penalty is reported in the loss but must not flow
            # gradients (d||w||/dw is NaN at the zero blocks prox creates)
            reg = tree_group_penalty(params, cfg.sparsity.block_shape, 2,
                                     cfg.sparsity.applies_to)
            loss = loss + cfg.sparsity.lambda_reg * jax.lax.stop_gradient(reg)
        return loss, {"ce": ce, "aux": aux}
    return loss_fn


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                    grad_accum: int = 1, packs=None):
    loss_fn = make_loss_fn(cfg, packs)

    def train_step(params, opt_state, batch):
        if grad_accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            # microbatched accumulation: batch dims reshaped (A, B/A, ...)
            def micro(carry, mb):
                gsum, lsum = carry
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb)
                return (jax.tree_util.tree_map(jnp.add, gsum, g),
                        lsum + l), None
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape(grad_accum, x.shape[0] // grad_accum,
                                    *x.shape[1:]), batch)
            (gsum, lsum), _ = jax.lax.scan(micro, (zeros, 0.0), mbs)
            grads = jax.tree_util.tree_map(lambda g: g / grad_accum, gsum)
            loss = lsum / grad_accum
            metrics = {"ce": loss, "aux": jnp.zeros((), jnp.float32)}
        new_params, new_opt, om = adamw_update(grads, opt_state, params,
                                               opt_cfg, cfg.sparsity)
        return new_params, new_opt, {"loss": loss, **metrics, **om}

    return train_step


def make_prefill_step(cfg: ModelConfig, packs=None):
    def prefill_step(params, batch):
        logits, _ = model_forward(params, cfg, batch, packs=packs)
        return jnp.argmax(logits[:, -1], axis=-1)
    return prefill_step


def make_serve_step(cfg: ModelConfig, packs=None):
    def serve_step(params, cache, token, pos):
        logits, new_cache, _ = model_decode_step(params, cache, cfg, token,
                                                 pos, packs=packs)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return nxt[:, None], new_cache
    return serve_step
