"""The encoder cells' whole run on the CPU at rehearsal sizes: correct when
sound, and not correct with the timed path broken underneath or with the
control (the W8A8 float8 reference) in the program's place."""
import json
import os
import sys

import jax.numpy as jnp
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import control  # noqa: E402  (bench/control.py)
import run as R  # noqa: E402

QA, CLS = "bert-base-s80.qa384-b1", "bert-base-s80.cls128-b32"


def _line(capsys, cell, seed=7):
    assert R.execute(cell, seed, 0.5, False, True) == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["rehearsal"] is True and "metrics" not in line
    return line


@pytest.mark.parametrize("cell", [QA, CLS])
def test_sound_run_is_correct(capsys, cell):
    line = _line(capsys, cell)
    assert line["correct"] is True, line["checks"]
    assert line["compilations_in_window"] == 0


def test_answer_altered_where_produced(capsys, monkeypatch):
    from repro.serving.servable import Servable
    orig = Servable.forward

    def forward(self, batch):
        return orig(self, batch).at[0, 0, 0].add(1.0)
    monkeypatch.setattr(Servable, "forward", forward)
    line = _line(capsys, QA)
    assert line["correct"] is False
    assert line["checks"]["logit_err"]["value"] > \
        line["checks"]["logit_err"]["limit"]


def test_half_of_the_batch_left_out(capsys, monkeypatch):
    from repro.serving.servable import Servable
    orig = Servable.forward

    def forward(self, batch):
        x = jnp.asarray(batch)
        half = x.shape[0] // 2
        out = orig(self, x[:half])
        return jnp.concatenate([out, out], axis=0)
    monkeypatch.setattr(Servable, "forward", forward)
    assert _line(capsys, CLS)["correct"] is False


@pytest.mark.parametrize("seed", [3, 4])
def test_control_fails_the_limit(seed):
    r = control.readings(QA, seed, 0.5, rehearsal=True)
    assert r["program"]["correct"] is True, r["program"]["checks"]
    assert r["control"]["correct"] is False
    c = r["control"]["checks"]["logit_err"]
    assert c["value"] > c["limit"]
