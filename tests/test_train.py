"""Integration: training loss goes down, checkpoint/restart is exact,
injected failures recover, elastic restore re-shards, straggler rebalance,
gradient compression numerics."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import CheckpointStore
from repro.configs.registry import get_config
from repro.core.sparsity import SparsityConfig, actual_sparsity
from repro.data.pipeline import DataConfig, DataPipeline
from repro.launch.mesh import make_mesh
from repro.launch.train import TrainConfig, Trainer
from repro.optim.adamw import AdamWConfig
from repro.optim.compression import (CompressionConfig, compress,
                                     decompress, init_error_buffers)
from repro.runtime.fault_tolerance import (FaultInjector,
                                           FaultToleranceConfig,
                                           StragglerMonitor)

single_mesh = lambda: make_mesh((1, 1), ("data", "model"))


def _tcfg(tmp, **kw):
    return TrainConfig(
        n_steps=kw.pop("n_steps", 12), ckpt_dir=str(tmp),
        opt=AdamWConfig(peak_lr=5e-3, warmup_steps=2, total_steps=50,
                        weight_decay=0.0),
        ft=FaultToleranceConfig(checkpoint_every=4, max_restarts=3),
        log_every=1, **kw)


def _dcfg(cfg):
    return DataConfig(seq_len=32, global_batch=4, vocab_size=cfg.vocab_size)


def test_loss_decreases(tmp_path):
    cfg = get_config("deepseek_7b", smoke=True)
    tr = Trainer(cfg, _tcfg(tmp_path, n_steps=20), single_mesh(), _dcfg(cfg))
    _, hist = tr.fit(resume=False)
    first = np.mean([l for _, l in hist[:3]])
    last = np.mean([l for _, l in hist[-3:]])
    assert last < first, hist


def test_failure_recovery_resumes_from_checkpoint(tmp_path):
    cfg = get_config("deepseek_7b", smoke=True)
    inj = FaultInjector(fail_at_steps=(6, 9))
    tr = Trainer(cfg, _tcfg(tmp_path), single_mesh(), _dcfg(cfg),
                 fault_injector=inj)
    state, hist = tr.fit(resume=False)
    assert inj.fired == {6, 9}
    assert int(state["opt"]["step"]) == 12    # completed despite failures


def test_restart_reproduces_uninterrupted_run(tmp_path):
    """Deterministic data + restore-on-failure => same final loss as a
    clean run (exactly-once step semantics)."""
    cfg = get_config("deepseek_7b", smoke=True)
    t1 = Trainer(cfg, _tcfg(tmp_path / "a"), single_mesh(), _dcfg(cfg))
    s1, h1 = t1.fit(resume=False)
    inj = FaultInjector(fail_at_steps=(7,))
    t2 = Trainer(cfg, _tcfg(tmp_path / "b"), single_mesh(), _dcfg(cfg),
                 fault_injector=inj)
    s2, h2 = t2.fit(resume=False)
    l1 = jax.tree_util.tree_leaves(s1["params"])
    l2 = jax.tree_util.tree_leaves(s2["params"])
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_checkpoint_elastic_restore(tmp_path):
    """Save under one mesh, restore under a different device layout."""
    cfg = get_config("deepseek_7b", smoke=True)
    tr = Trainer(cfg, _tcfg(tmp_path, n_steps=4), single_mesh(), _dcfg(cfg))
    state, _ = tr.fit(resume=False)
    store = CheckpointStore(str(tmp_path))
    like = {"params": state["params"], "opt": state["opt"], "masks": None}
    restored = store.restore(like)
    for a, b in zip(jax.tree_util.tree_leaves(state["params"]),
                    jax.tree_util.tree_leaves(restored["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_gradual_pruning_during_training(tmp_path):
    sp = SparsityConfig(block_shape=(8, 8), sparsity=0.75,
                        targets=("attn/wq", "attn/wk", "attn/wv", "attn/wo"),
                        start_step=0, end_step=8)
    cfg = dataclasses.replace(get_config("deepseek_7b", smoke=True),
                              sparsity=sp)
    tr = Trainer(cfg, _tcfg(tmp_path, n_steps=12, prune=True), single_mesh(),
                 _dcfg(cfg))
    state, hist = tr.fit(resume=False)
    w = state["params"]["blocks"][0]["attn"]["wq"]["w"][0]
    got = float(actual_sparsity(w, (8, 8)))
    assert got >= 0.70, got


def test_data_pipeline_determinism_and_sharding():
    cfg = DataConfig(seq_len=16, global_batch=8, vocab_size=100, n_hosts=1)
    p = DataPipeline(cfg)
    b5a = p.batch_at(5)
    b5b = p.batch_at(5)
    np.testing.assert_array_equal(b5a["tokens"], b5b["tokens"])
    p.close()
    # host sharding partitions the batch
    c0 = DataConfig(seq_len=16, global_batch=8, vocab_size=100, n_hosts=2,
                    host_id=0)
    assert c0.host_batch == 4


def test_straggler_monitor_rebalances():
    mon = StragglerMonitor(4, FaultToleranceConfig(straggler_threshold=1.4))
    for _ in range(5):
        mon.observe({0: 1.0, 1: 1.0, 2: 1.0, 3: 3.0})
    assert mon.stragglers() == [3]
    mb = mon.rebalance(np.array([4, 4, 4, 4]))
    assert mb.sum() == 16 and mb[3] == 3


class TestCompression:
    def test_roundtrip_identity_at_full_density(self):
        rng = np.random.RandomState(0)
        g = jnp.asarray(rng.randn(64, 256).astype(np.float32))
        ccfg = CompressionConfig(block_shape=(8, 128), density=1.0,
                                 min_size=0)
        err0 = jnp.zeros_like(g)
        vals, idx, err = compress(g, err0, ccfg)
        back = decompress(vals, idx, g.shape, ccfg)
        np.testing.assert_allclose(np.asarray(back), np.asarray(g),
                                   rtol=1e-6)
        assert float(jnp.abs(err).max()) == 0.0

    def test_error_feedback_conserves_signal(self):
        """compressed + error == original (nothing lost, only deferred)."""
        rng = np.random.RandomState(1)
        g = jnp.asarray(rng.randn(64, 256).astype(np.float32))
        ccfg = CompressionConfig(block_shape=(8, 128), density=0.25,
                                 min_size=0)
        vals, idx, err = compress(g, jnp.zeros_like(g), ccfg)
        back = decompress(vals, idx, g.shape, ccfg)
        np.testing.assert_allclose(np.asarray(back + err), np.asarray(g),
                                   rtol=1e-5, atol=1e-6)

    def test_compressed_allreduce_under_shard_map(self):
        from repro.optim.compression import make_compressed_sync
        mesh = make_mesh((1,), ("data",))
        ccfg = CompressionConfig(block_shape=(8, 128), density=1.0,
                                 min_size=0)
        rng = np.random.RandomState(2)
        g = jnp.asarray(rng.randn(16, 256).astype(np.float32))
        err = jnp.zeros_like(g)
        sync = make_compressed_sync(mesh, ("data",), ccfg)
        out, new_err = sync(g, err)
        np.testing.assert_allclose(np.asarray(out), np.asarray(g), rtol=1e-5)


def test_group_lasso_prox_induces_sparsity_without_pruning(tmp_path):
    """Paper Eq. 1 mechanism: the group-lasso prox term ALONE (no magnitude
    pruning) drives whole blocks to exact zero during training."""
    # block norm at init ~ 0.02*8 = 0.16; per-step shrink = lr * lambda,
    # so lambda = 3.0 crosses the weakest blocks well inside 40 steps
    sp = SparsityConfig(block_shape=(8, 8), sparsity=0.0, lambda_reg=3.0,
                        targets=("attn/wq", "attn/wk", "attn/wv", "attn/wo"))
    cfg = dataclasses.replace(get_config("deepseek_7b", smoke=True),
                              sparsity=sp)
    tr = Trainer(cfg, _tcfg(tmp_path, n_steps=40), single_mesh(), _dcfg(cfg))
    state, _ = tr.fit(resume=False)
    w = state["params"]["blocks"][0]["attn"]["wq"]["w"][0]
    got = float(actual_sparsity(w, (8, 8)))
    assert got > 0.10, f"prox produced no block sparsity ({got})"


def test_elastic_restore_across_mesh_shapes(tmp_path):
    """Save on a (1,1) mesh; restore + step on a different layout in a
    subprocess with 4 fake devices (scale-up restart)."""
    import os
    import subprocess
    import sys
    cfg = get_config("deepseek_7b", smoke=True)
    tr = Trainer(cfg, _tcfg(tmp_path, n_steps=4), single_mesh(), _dcfg(cfg))
    tr.fit(resume=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = f"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
import jax, numpy as np
from repro.configs.registry import get_config
from repro.data.pipeline import DataConfig
from repro.launch.mesh import make_mesh
from repro.launch.train import TrainConfig, Trainer
from repro.optim.adamw import AdamWConfig
from repro.runtime.fault_tolerance import FaultToleranceConfig
cfg = get_config('deepseek_7b', smoke=True)
mesh = make_mesh((2, 2), ('data', 'model'))
tcfg = TrainConfig(n_steps=6, ckpt_dir={str(tmp_path)!r},
                   opt=AdamWConfig(peak_lr=5e-3, warmup_steps=2,
                                   total_steps=50, weight_decay=0.0),
                   ft=FaultToleranceConfig(checkpoint_every=100), log_every=1)
dcfg = DataConfig(seq_len=32, global_batch=4, vocab_size=cfg.vocab_size)
tr = Trainer(cfg, tcfg, mesh, dcfg)
state, hist = tr.fit(resume=True)   # restores the (1,1)-mesh checkpoint
assert int(state['opt']['step']) == 6, int(state['opt']['step'])
print('ELASTIC OK', hist[-1])
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ELASTIC OK" in r.stdout
