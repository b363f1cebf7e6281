"""The readers of the held experts' metrics: ``moe_rows_per_expert.lm`` on
synthetic span records and on what the V2-Lite engine recorded under a
profiler session (None where nothing carries the counters, as on a program
that does not count), and the required work that ``moe_gmm_roofline.lm``
divides by."""
import os
import sys

import jax
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as R  # noqa: E402
from lib import spans as SP  # noqa: E402
from lib import work_mla_moe as WM  # noqa: E402
from repro.runtime import trace  # noqa: E402

MS = 1_000_000  # ns
ROWS = "moe_rows_per_expert.lm"


def _sp(name, t0_ms, **args):
    return trace.Span(name, int(t0_ms * MS), int((t0_ms + 1) * MS), args)


def _read(name):
    return R.load_reader(name).read({"record": {"window_s": 1.0},
                                     "trace": {"window_s": 1.0}})


def test_rows_per_expert_on_synthetic_records(monkeypatch):
    # a prefill of 40 rows and two windows of 96 and 80, over 16 experts x
    # 26 layers per forward: 1 + 8 + 8 forwards
    recs = [_sp(SP.ADMIT, 0, req=1, tokens=8, moe_rows=40,
                moe_expert_slots=416),
            _sp(SP.ADMIT, 1, req=2, tokens=0),             # parked: none
            _sp(SP.WINDOW, 2, k=8, slots=4, tokens=30, moe_rows=96,
                moe_expert_slots=3328),
            _sp(SP.WINDOW, 3, k=8, slots=4, tokens=30, moe_rows=80,
                moe_expert_slots=3328)]
    monkeypatch.setattr(SP, "program_spans",
                        lambda name: [r for r in recs if r.name == name])
    assert _read(ROWS) == pytest.approx(216 / 7072)


@pytest.mark.parametrize("recs", [[], [_sp(SP.WINDOW, 0, k=8, slots=4,
                                                 tokens=3)]])
def test_nothing_to_read_reads_none(monkeypatch, recs):
    monkeypatch.setattr(SP, "program_spans",
                        lambda name: [r for r in recs if r.name == name])
    assert _read(ROWS) is None


def test_reads_what_the_engine_recorded(tmp_path):
    from repro.configs.registry import get_config
    from repro.models import init_model
    from repro.serving import ServingSpec, prepare_servable
    cfg = get_config("deepseek_v2_lite_16b", smoke=True)
    sv = prepare_servable(init_model(jax.random.PRNGKey(0), cfg), cfg,
                          ServingSpec(tile=(16, 16)))
    eng = sv.engine(max_slots=2, cache_len=32, sync_every=4)
    rng = np.random.RandomState(0)
    trace.clear()
    try:
        with jax.profiler.trace(str(tmp_path)):
            for n in (5, 9):
                eng.submit(rng.randint(0, cfg.vocab_size, (n,)).tolist(),
                           max_new_tokens=6)
            eng.run()
        st = eng.stats
        # every assignment of a real token: (14 prompt + 10 decode tokens)
        # x top-2 x 2 MoE layers, all 8 experts held
        assert st.moe_rows == (14 + 10) * 2 * 2
        assert st.moe_expert_slots == 8 * 2 * (st.prefills + st.steps)
        assert _read(ROWS) == pytest.approx(st.moe_rows
                                            / st.moe_expert_slots)
    finally:
        trace.clear()


def test_decode_step_reads_the_weights_once():
    """A decode step's bytes are the weights (the held experts, packs, the
    LM head) once, whatever the number of rows, plus each row's cache."""
    from families import mla_moe as F
    _, _, conf, mix, _ = R.resolve("deepseek-v2-lite-ep4-s70.chat-c64")
    one, many = ([[(400, 50, 1, False)] * m] for m in (1, 64))
    b1 = F.work(conf, mix, one)["steps"][0][1][1]
    b64 = F.work(conf, mix, many)["steps"][0][1][1]
    assert 8e9 < b1 < b64 < 1.2 * b1


def test_moe_gmm_work_counts_rows_from_shapes():
    # 64 decode tokens x top-6 x 16 of 64 experts: 96 rows; the 16 held
    # experts' three 2048 x 1408 bf16 weights read once
    flops, nbytes = WM.moe_gmm(64, 6, 16, 64, 2048, 1408)
    assert flops == 6.0 * 96 * 2048 * 1408
    assert nbytes == 3 * 16 * 2048 * 1408 * 2 + 96 * 2048 * 6
