"""Paper Figure 2 analogue: performance vs block shape, plus the reuse
mechanism the paper hypothesizes (unique intra-block pattern cardinality).

Consumes table1 results when available (same process) or re-derives the
mechanism metrics standalone: for each block shape, at 80% sparsity,
  * packed tile density (compute actually executed by the BSR path)
  * unique intra-block pattern count / #blocks (TVM-scheduler reuse proxy)

Output CSV: name,us_per_call,derived
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.core.pattern_reuse import count_unique_intrablock_patterns
from repro.core.pruner import oneshot_prune
from repro.core.sparsity import SparsityConfig
from repro.kernels import pack_bsr
from repro.models import init_model
from repro.runtime.compile_cache import use_compile_cache

from benchmarks.table1_block_sweep import BLOCK_SHAPES, SPARSITY, _TARGETS


def run(emit=print):
    cfg = get_config("bert_base")
    params = init_model(jax.random.PRNGKey(0), cfg)
    w_ref = None
    out = []
    for name, bs in BLOCK_SHAPES:
        sp = SparsityConfig(block_shape=bs, sparsity=SPARSITY,
                            targets=_TARGETS)
        pruned, _ = oneshot_prune(params, sp)
        w = np.asarray(pruned["layers"][0]["attn"]["wq"]["w"], np.float32)
        tile = bs if bs != (1, 1) else (32, 32)
        pk = pack_bsr(w, tile)
        n_blocks = (w.shape[0] // bs[0]) * (w.shape[1] // bs[1])
        uniq = count_unique_intrablock_patterns(w, bs) / n_blocks
        emit(f"fig2/density_{name},0,{pk.density:.4f}")
        emit(f"fig2/unique_pattern_frac_{name},0,{uniq:.4f}")
        out.append((name, pk.density, uniq))
    return out


if __name__ == "__main__":
    use_compile_cache()
    run()
