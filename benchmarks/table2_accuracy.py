"""Paper Table 2 analogue: task quality vs sparsity ratio.

No GLUE/SQuAD data offline, so the proxy task is synthetic masked-LM on a
structured token stream (zipfian unigram + copy patterns): train a reduced
BERT dense, then prune to 50% / 80% block sparsity (32x1 blocks, the paper's
regularization shape) with brief finetuning, and report MLM loss + masked
accuracy for each arm. The claim being reproduced is the TREND (small quality
drop at 50%, modest at 80%), not absolute GLUE numbers.

Output CSV: name,us_per_call,derived  (us=finetune step time, derived=metric)
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.core.pruner import apply_masks, oneshot_prune
from repro.core.sparsity import SparsityConfig
from repro.launch.steps import cross_entropy
from repro.models import bert as bert_mod
from repro.models import init_model
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state
from repro.runtime.compile_cache import use_compile_cache

MASK_ID = 3
_TARGETS = ("attn/wq", "attn/wk", "attn/wv", "attn/wo", "ffn/wi", "ffn/wo")


def _mlm_batch(rng, cfg, b=8, s=64):
    base = rng.zipf(1.5, size=(b, s)) % (cfg.vocab_size - 4) + 4
    # copy structure: second half repeats first half (learnable signal)
    base[:, s // 2:] = base[:, : s // 2]
    mask = rng.rand(b, s) < 0.15
    tokens = np.where(mask, MASK_ID, base)
    return (jnp.asarray(tokens.astype(np.int32)),
            jnp.asarray(base.astype(np.int32)), jnp.asarray(mask))


def _mlm_loss(params, cfg, tokens, labels, mask):
    logits = bert_mod.forward(params, cfg, tokens)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    per_tok = lse - gold
    m = mask.astype(jnp.float32)
    loss = jnp.sum(per_tok * m) / jnp.maximum(jnp.sum(m), 1.0)
    acc = jnp.sum((jnp.argmax(logits, -1) == labels) * m) / \
        jnp.maximum(jnp.sum(m), 1.0)
    return loss, acc


def _train(params, cfg, steps, rng, masks=None, sp=None, lr=3e-4):
    opt_cfg = AdamWConfig(peak_lr=lr, warmup_steps=10, total_steps=steps,
                          weight_decay=0.0)
    opt = init_opt_state(params, opt_cfg)

    @jax.jit
    def step(p, o, tokens, labels, mask):
        (l, acc), g = jax.value_and_grad(
            lambda p_: _mlm_loss(p_, cfg, tokens, labels, mask),
            has_aux=True)(p)
        p2, o2, _ = adamw_update(g, o, p, opt_cfg)
        return p2, o2, l

    t_step = None
    for i in range(steps):
        tokens, labels, mask = _mlm_batch(rng, cfg)
        t0 = time.perf_counter()
        params, opt, loss = step(params, opt, tokens, labels, mask)
        jax.block_until_ready(loss)
        t_step = time.perf_counter() - t0
        if masks is not None:
            params = apply_masks(params, masks, sp)
    return params, float(loss), t_step


def run(pretrain_steps=150, finetune_steps=60, emit=print):
    cfg = dataclasses.replace(get_config("bert_base", smoke=True),
                              n_layers=4, d_model=128, n_heads=4,
                              n_kv_heads=4, head_dim=32, d_ff=512,
                              vocab_size=1024)
    rng = np.random.RandomState(0)
    params = init_model(jax.random.PRNGKey(0), cfg)
    params, _, t_step = _train(params, cfg, pretrain_steps, rng)

    def evaluate(p):
        ls, accs = [], []
        erng = np.random.RandomState(999)
        for _ in range(8):
            tokens, labels, mask = _mlm_batch(erng, cfg)
            l, a = jax.jit(lambda p_, t, y, m: _mlm_loss(p_, cfg, t, y, m)
                           )(p, tokens, labels, mask)
            ls.append(float(l))
            accs.append(float(a))
        return float(np.mean(ls)), float(np.mean(accs))

    l_dense, a_dense = evaluate(params)
    emit(f"table2/dense_mlm_acc,{t_step*1e6:.0f},{a_dense:.4f}")
    emit(f"table2/dense_mlm_loss,{t_step*1e6:.0f},{l_dense:.4f}")
    results = {"dense": (l_dense, a_dense)}

    for ratio in (0.5, 0.8):
        sp = SparsityConfig(block_shape=(32, 1), sparsity=ratio,
                            targets=_TARGETS)
        pruned, masks = oneshot_prune(params, sp)
        tuned, _, t_ft = _train(pruned, cfg, finetune_steps,
                                np.random.RandomState(1), masks=masks, sp=sp,
                                lr=1e-4)
        l, a = evaluate(tuned)
        results[f"{int(ratio*100)}%"] = (l, a)
        emit(f"table2/sparse{int(ratio*100)}_mlm_acc,{t_ft*1e6:.0f},{a:.4f}")
        emit(f"table2/sparse{int(ratio*100)}_mlm_loss,{t_ft*1e6:.0f},{l:.4f}")
    return results


if __name__ == "__main__":
    use_compile_cache()
    run()
