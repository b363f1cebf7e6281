"""Quickstart: the paper's algorithm->compilation co-design flow in 50 lines.

  1. take a BERT encoder, declare the co-design as ONE ServingSpec
     (block pruning recipe + tile + fusion/union + backend)
  2. prepare_servable runs prune -> BSR export -> exec plans -> registry
  3. serve through the block-sparse kernels; verify parity with dense
  4. inspect stats(): density, union overhead, pattern reuse
  5. save / load_servable: export cost is paid once per model

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.core.pruner import sparsity_report, tied_prune
from repro.models import init_model, model_forward
from repro.runtime.compile_cache import use_compile_cache
from repro.serving import ServingSpec, load_servable, prepare_servable


def main():
    use_compile_cache()
    cfg = get_config("bert_base", smoke=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, 48)))

    # 1. + 2. one spec, one call (paper Eq. 3 pruning + TVM-analogue export).
    # We prune outside the facade (prune='none') only because step 3's dense
    # parity check needs the pruned dense tree too; prune='tied' would run
    # the same recipe inside prepare_servable.
    spec = ServingSpec(tile=(16, 16), sparsity=0.8, prune="none")
    pruned, _ = tied_prune(params, spec.sparsity_config())
    servable = prepare_servable(pruned, cfg, spec)

    print("per-weight block sparsity:",
          {k.split('/')[-2]: round(v, 2) for k, v in
           list(sparsity_report(pruned, spec.sparsity_config()).items())[:4]})

    # 3. sparse serving parity vs dense execution of the same pruned weights
    dense_out, _ = model_forward(pruned, cfg, {"tokens": toks})
    sparse_out = servable.forward(toks)
    err = float(jnp.max(jnp.abs(dense_out - sparse_out)))
    print(f"dense-vs-BSR max |delta logits| = {err:.2e}")

    # 4. the co-design scorecard
    st = servable.stats()
    print(f"stats: density {st['density']:.2f}, union overhead "
          f"{st['union_overhead']:.2f}x, {st['unique_patterns']} unique "
          f"patterns for {st['packed_projections']} projections, registry "
          f"{st['registry']['hits']} hits / {st['registry']['misses']} misses")

    # 5. persistence: serve again without re-running the export
    with tempfile.TemporaryDirectory() as ckpt:
        servable.save(ckpt)
        reloaded = load_servable(ckpt)
        err = float(jnp.max(jnp.abs(reloaded.forward(toks) - sparse_out)))
        print(f"save -> load_servable round-trip delta = {err:.2e}")


if __name__ == "__main__":
    main()
