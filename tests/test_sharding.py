"""Sharding rules: valid divisibility-aware specs; small-mesh end-to-end
pjit execution; subprocess dry-run smoke (own XLA_FLAGS, 16 fake devices)."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.registry import get_config
from repro.launch.mesh import make_mesh
from repro.launch.sharding import (batch_shardings, cache_shardings,
                                   param_shardings, spec_for_cache,
                                   spec_for_param)
from repro.launch.specs import cache_specs, input_specs, params_specs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def test_make_mesh_axes_are_auto():
    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,) * 2


class TestSpecRules:
    def test_column_vs_row_parallel(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        # a 16x16-divisible fake weight
        assert spec_for_param("blocks/0/attn/wq/w", (16, 4096, 4096), mesh) \
            == P(None, "model", "data")
        assert spec_for_param("blocks/0/attn/wo/w", (16, 4096, 4096), mesh) \
            == P(None, "data", "model")

    def test_indivisible_dims_replicate(self):
        # abstract mesh: spec rules shouldn't need real devices
        wide = jax.sharding.AbstractMesh((1, 16), ("data", "model"))
        spec = spec_for_param("blocks/0/attn/wk/w", (2, 100, 4096), wide)
        assert spec[1] is None     # 100 % 16 != 0 -> replicated
        assert spec[2] == "data"   # in-dim divisible by data axis -> FSDP
        spec2 = spec_for_param("blocks/0/attn/wq/w", (2, 4096, 4096), wide)
        assert spec2 == P(None, "model", "data")

    def test_expert_weights_get_ep(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        spec = spec_for_param("blocks/0/ffn/wi", (16, 128, 4096, 1536), mesh)
        assert spec == P(None, "model", "data", None)

    def test_cache_specs_avoid_head_dim(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        spec = spec_for_cache("blocks/0/mix/k", (16, 128, 32768, 4, 256),
                              mesh)
        assert spec[4] is None     # head_dim never sharded over model

    def test_all_archs_all_shapes_specs_build(self):
        mesh = _mesh()
        for arch in ("qwen3_moe_235b_a22b", "whisper_base", "mamba2_780m",
                     "pixtral_12b"):
            cfg = get_config(arch, smoke=True)
            p = params_specs(cfg)
            sh = param_shardings(p, mesh)
            assert jax.tree_util.tree_structure(sh) == \
                jax.tree_util.tree_structure(p)


def test_pjit_train_step_runs_on_mesh():
    """End-to-end sharded execution on the (1,1) CPU mesh."""
    from repro.launch.steps import make_train_step
    from repro.models import init_model
    from repro.optim.adamw import AdamWConfig, init_opt_state
    cfg = get_config("deepseek_7b", smoke=True)
    mesh = _mesh()
    rng = np.random.RandomState(0)
    with mesh:
        params = init_model(jax.random.PRNGKey(0), cfg)
        opt_cfg = AdamWConfig()
        opt = init_opt_state(params, opt_cfg)
        p_sh = param_shardings(jax.eval_shape(lambda: params), mesh)
        batch = {"tokens": rng.randint(0, cfg.vocab_size, (2, 16)),
                 "labels": rng.randint(0, cfg.vocab_size, (2, 16))}
        step = jax.jit(make_train_step(cfg, opt_cfg), in_shardings=(p_sh, None, None))
        p2, o2, m = step(params, opt, batch)
        assert np.isfinite(float(m["loss"]))


@pytest.mark.slow
def test_dryrun_subprocess_smoke(tmp_path):
    """Real dryrun.py entry point with its own XLA_FLAGS in a subprocess
    (16 fake devices via DRYRUN_DEVICES; prod-mesh shape shrunk by env)."""
    env = dict(os.environ, DRYRUN_DEVICES="16",
               PYTHONPATH=os.path.join(REPO, "src"))
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=16'\n"
        "import jax\n"
        "from repro.configs.registry import get_config\n"
        "from repro.launch.dryrun import lower_cell\n"
        "from repro.launch.mesh import make_mesh\n"
        "mesh = make_mesh((4,4),('data','model'))\n"
        "c = lower_cell(get_config('whisper_base', smoke=True), 'train_4k', mesh)\n"
        "compiled = c.compile()\n"
        "print('MEM', compiled.memory_analysis() is not None)\n"
        "print('COST', bool(compiled.cost_analysis()))\n"
    )
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MEM True" in out.stdout and "COST True" in out.stdout
