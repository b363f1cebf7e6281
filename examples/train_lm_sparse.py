"""Fault-tolerant sparse training driver: a decoder LM trained with gradual
block pruning + group-lasso prox, an injected mid-run failure, automatic
checkpoint restore, and a final handoff to the serving facade
(``prepare_servable`` with ``prune='none'``: the trained masks ARE the
sparsity) -- the whole substrate in one run.

Run:  PYTHONPATH=src python examples/train_lm_sparse.py [--steps 60]
"""
import argparse
import dataclasses
import logging
import tempfile

from repro.configs.registry import get_config
from repro.core.pruner import sparsity_report
from repro.core.sparsity import SparsityConfig
from repro.data.pipeline import DataConfig
from repro.launch.mesh import make_mesh
from repro.launch.train import TrainConfig, Trainer
from repro.optim.adamw import AdamWConfig
from repro.runtime.compile_cache import use_compile_cache
from repro.serving import ServingSpec, prepare_servable
from repro.runtime.fault_tolerance import FaultInjector, FaultToleranceConfig

logging.basicConfig(level=logging.INFO,
                    format="%(asctime)s %(name)s %(message)s")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--arch", default="deepseek_7b")
    args = ap.parse_args()
    use_compile_cache()

    sp = SparsityConfig(block_shape=(16, 16), sparsity=0.7,
                        lambda_reg=1e-4, start_step=10,
                        end_step=max(args.steps - 10, 11))
    cfg = dataclasses.replace(get_config(args.arch, smoke=True), sparsity=sp)
    mesh = make_mesh((1, 1), ("data", "model"))
    ckpt = tempfile.mkdtemp(prefix="repro_lm_")

    tcfg = TrainConfig(
        n_steps=args.steps, ckpt_dir=ckpt, prune=True, log_every=10,
        opt=AdamWConfig(peak_lr=3e-3, warmup_steps=10,
                        total_steps=args.steps, weight_decay=0.0),
        ft=FaultToleranceConfig(checkpoint_every=15, max_restarts=3))
    data = DataConfig(seq_len=64, global_batch=8, vocab_size=cfg.vocab_size)

    injector = FaultInjector(fail_at_steps=(args.steps // 2,))
    trainer = Trainer(cfg, tcfg, mesh, data, fault_injector=injector)
    state, history = trainer.fit(resume=False)

    print("\nloss curve:", [f"{s}:{l:.3f}" for s, l in history])
    print("injected failures survived:", sorted(injector.fired))
    rep = sparsity_report(state["params"], sp)
    print("final attention block sparsity:",
          {k.split('/')[-2]: round(v, 2) for k, v in list(rep.items())[:4]})

    servable = prepare_servable(state["params"], cfg,
                                ServingSpec(tile=(16, 16), prune="none"))
    st = servable.stats()
    print(f"BSR export: {st['packed_projections']} weights, mean density "
          f"{st['density']:.2f}, union overhead {st['union_overhead']:.2f}")


if __name__ == "__main__":
    main()
