"""The program against the V2-Lite reference (``references/mla_moe.py``,
which imports nothing of the program) at the rehearsal sizes, in float32:
a dense layer and two MoE layers of which this chip holds half the
experts, YaRN latent attention, packed projections. Prefill, then decode
through the paged latent cache: every logit the engine produced equals the
reference's full forward pass at that position."""
import copy
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as R  # noqa: E402
from families import lm as LM  # noqa: E402
from families import mla_moe as F  # noqa: E402
from references import mla_moe as ref  # noqa: E402

CELL = "deepseek-v2-lite-ep4-s70.chat-c64"


@pytest.fixture(scope="module")
def served():
    from repro.serving import prepare_servable
    _, _, conf, _, _ = R.resolve(CELL, rehearsal=True)
    conf = dict(copy.deepcopy(conf), dtype="float32")
    assert ref.dims(conf)["L"] - ref.dims(conf)["dense"] >= 2
    key = jax.random.PRNGKey(5)
    params = jax.jit(lambda k: ref.init(conf, k, jnp.float32))(key)
    sv = prepare_servable(params, F.model_config(conf),
                          LM.serving_spec(conf))
    assert sv.stats()["packed_projections"] > 0
    return conf, key, sv


@pytest.mark.parametrize("kv_layout", ["paged", "dense"])
def test_engine_logits_match_the_reference(served, kv_layout):
    conf, key, sv = served
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, conf["vocab_size"], (n,)) for n in (7, 19)]
    eng = sv.engine(max_slots=2, cache_len=48, collect_logits=True,
                    kv_layout=kv_layout)
    hs = [eng.submit(p.tolist(), max_new_tokens=6) for p in prompts]
    eng.run()
    assert eng.kv_layout == kv_layout
    seqs = [np.concatenate([p, np.asarray(h.tokens[:-1], np.int32)])
            for p, h in zip(prompts, hs)]
    rows = [np.arange(p.size - 1, p.size + 5) for p in prompts]
    want = ref.logits(conf, key, seqs, rows, dtype=jnp.float32)
    for h, w in zip(hs, want):
        assert h.status == "done" and len(h.step_logits) == 6
        got = np.stack(h.step_logits)
        # float32 on both sides: the program's sums (packed tiles, the
        # absorbed latent decode, the grouped experts) run in other orders
        # than the reference's, a few ulps per layer of logits of ~0.1
        np.testing.assert_allclose(got, np.asarray(w), rtol=0, atol=2e-5)
