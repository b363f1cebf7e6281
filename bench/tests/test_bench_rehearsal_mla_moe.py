"""The V2-Lite cell's whole run on the CPU at rehearsal sizes: correct when
sound, and not correct with a served token altered where it is produced or
with the control (the W8A8 float8 reference) in the program's place."""
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import control  # noqa: E402  (bench/control.py)
import run as R  # noqa: E402

CELL = "deepseek-v2-lite-ep4-s70.chat-c64"


def test_sound_run_is_correct(capsys):
    assert R.execute(CELL, 3000000123, 1.0, False, True) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["rehearsal"] is True and "metrics" not in line
    assert line["correct"] is True, line["checks"]
    assert line["compilations_in_window"] == 0
    assert line["attempted"] > 0 and line["failed"] == 0


def test_token_altered_and_control_fail_the_limit(monkeypatch):
    r = control.readings(CELL, 6, 1.0, rehearsal=True)
    assert r["program"]["correct"] is True, r["program"]["checks"]
    assert r["control"]["correct"] is False
    c = r["control"]["checks"]["logit_gap"]
    assert c["value"] > c["limit"]

    from repro.models import api
    orig = api.decode_many

    def decode_many(*a, **k):
        toks, valid, state = orig(*a, **k)
        return (toks.at[-1].set((toks[-1] + 1) % 97), valid, state)
    monkeypatch.setattr(api, "decode_many", decode_many)
    r = control.readings(CELL, 6, 1.0, rehearsal=True)
    c = r["program"]["checks"]["logit_gap"]
    assert r["program"]["correct"] is False and c["value"] > c["limit"]
