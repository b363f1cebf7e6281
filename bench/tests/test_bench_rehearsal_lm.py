"""The decoder cells' whole run on the CPU at rehearsal sizes: correct when
sound, and not correct with a served token altered where it is produced or
with the control (the W8A8 float8 reference) in the program's place."""
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import control  # noqa: E402  (bench/control.py)
import run as R  # noqa: E402

CHAT = "deepseek-llm-7b-8L-s70.chat-c16"


def _line(capsys, cell, seed=7):
    assert R.execute(cell, seed, 1.0, False, True) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["rehearsal"] is True and "metrics" not in line
    return line


def test_sound_run_is_correct(capsys):
    line = _line(capsys, CHAT)
    assert line["correct"] is True, line["checks"]
    assert line["compilations_in_window"] == 0
    assert line["attempted"] > 0 and line["failed"] == 0


def test_token_altered_where_produced(capsys, monkeypatch):
    from repro.models import api

    orig = api.decode_many

    def decode_many(*a, **k):
        toks, valid, state = orig(*a, **k)
        return (toks.at[-1].set((toks[-1] + 1) % 97), valid, state)
    monkeypatch.setattr(api, "decode_many", decode_many)
    line = _line(capsys, CHAT)
    assert line["correct"] is False
    assert line["checks"]["logit_gap"]["value"] > \
        line["checks"]["logit_gap"]["limit"]


def test_control_fails_the_limit():
    r = control.readings(CHAT, 5, 1.0, rehearsal=True)
    assert r["program"]["correct"] is True, r["program"]["checks"]
    assert r["control"]["correct"] is False
    c = r["control"]["checks"]["logit_gap"]
    assert c["value"] > c["limit"]
