"""Paper Table 1 analogue: BERT_BASE CPU inference time vs sparsity structure.

Arms (mapping in DESIGN.md §2):
  eager      -- un-jitted jax.numpy          (PyTorch/TF row)
  xla_dense  -- jit dense                    (stock-TVM dense row)
  xla_masked -- jit, pruned weights, dense execution
                                             (stock TVM + sparse model row:
                                              the negative control)
  xla_bsr    -- jit, BSR-packed execution via the gather sparse path
                                             (TVM+ row)

Sweeps the paper's 14 block shapes at 80% sparsity on the full BERT_BASE
(L=12, H=768, seq 384, batch 1 -- the paper's SQuAD serving shape).
Irregular (1x1) sparsity is packed at the kernel's (32,32) tile granularity;
its packed density stays ~1.0, mechanically reproducing the paper's finding
that fine-grained sparsity yields no speedup without structure.

Output CSV: name,us_per_call,derived   (derived = ratio vs xla_dense)
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.core.pattern_reuse import count_unique_intrablock_patterns
from repro.core.sparsity import SparsityConfig
from repro.core.pruner import oneshot_prune
from repro.models import bert as bert_mod
from repro.models import init_model
from repro.runtime.compile_cache import use_compile_cache
from repro.serving import ServingSpec, prepare_servable

SEQ, BATCH, SPARSITY = 384, 1, 0.8
BLOCK_SHAPES = [
    ("irregular_1x1", (1, 1)),
    ("l1_1x4", (1, 4)), ("l1_1x8", (1, 8)), ("l1_1x16", (1, 16)),
    ("l1_1x32", (1, 32)), ("l1_1x64", (1, 64)), ("l1_1x128", (1, 128)),
    ("l1_1x256", (1, 256)), ("l1_1x384", (1, 384)),
    ("sq_4x4", (4, 4)), ("sq_8x8", (8, 8)), ("sq_16x16", (16, 16)),
    ("sq_32x32", (32, 32)), ("sq_64x64", (64, 64)),
    # beyond-paper: the XLA/TPU backend-tile optimum (EXPERIMENTS.md §Perf)
    ("sq_128x128", (128, 128)),
]
_TARGETS = ("attn/wq", "attn/wk", "attn/wv", "attn/wo", "ffn/wi", "ffn/wo")


def _time(fn, *args, reps=3, warmup=1):
    """Adaptive: configs slower than 5 s/run are measured once (noise is
    irrelevant at 10-50x slowdowns; budget matters on 1 CPU core)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    if first > 5.0 or reps <= 1:
        return first, 0.0
    ts = [first]
    for _ in range(reps - 1):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.mean(ts)), float(np.std(ts))


def run(reps=3, emit=lambda s: print(s, flush=True)):
    cfg = get_config("bert_base")
    params = init_model(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (BATCH, SEQ)))

    rows = []
    # -- dense baselines ---------------------------------------------------
    t_eager, _ = _time(lambda: bert_mod.forward(params, cfg, toks), reps=1)
    dense_fn = jax.jit(lambda p, t: bert_mod.forward(p, cfg, t))
    t_dense, s_dense = _time(dense_fn, params, toks, reps=reps)
    rows.append(("table1/eager_dense", t_eager, 1.0))
    rows.append(("table1/xla_dense", t_dense, 1.0))
    emit(f"table1/eager_dense,{t_eager*1e6:.0f},{t_eager/t_dense:.3f}")
    emit(f"table1/xla_dense,{t_dense*1e6:.0f},1.000")

    for name, bs in BLOCK_SHAPES:
        sp = SparsityConfig(block_shape=bs, sparsity=SPARSITY,
                            targets=_TARGETS)
        pruned, _ = oneshot_prune(params, sp)
        # negative control: pruned weights, dense execution
        t_masked, _ = _time(dense_fn, pruned, toks, reps=reps)
        # TVM+ analogue: BSR execution via the serving facade; kernel tile ==
        # sparsity block, except irregular which is packed at (32,32)
        tile = bs if bs != (1, 1) else (32, 32)
        servable = prepare_servable(
            pruned, cfg, ServingSpec(tile=tile, prune="none",
                                     cross_layer_union=False))
        density = servable.stats()["density"]
        t_bsr, s_bsr = _time(servable.forward, toks, reps=reps)
        ratio = t_bsr / t_dense
        uniq = count_unique_intrablock_patterns(
            np.asarray(pruned["layers"][0]["attn"]["wq"]["w"]), bs)
        emit(f"table1/masked_{name},{t_masked*1e6:.0f},"
             f"{t_masked/t_dense:.3f}")
        emit(f"table1/bsr_{name},{t_bsr*1e6:.0f},{ratio:.3f}")
        rows.append((name, t_masked, t_bsr, ratio, density, uniq))
    return rows


if __name__ == "__main__":
    use_compile_cache()
    run()
