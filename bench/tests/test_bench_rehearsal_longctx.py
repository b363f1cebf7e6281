"""The long-context cell (DeepSeek-LLM-7B, 12 clients, prompts of 2048 to
3584 at full size) run whole on the CPU at rehearsal sizes: correct when
sound, and its traffic offers one stratified set of lengths per round of
its clients."""
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as R  # noqa: E402
from lib import traffic as T  # noqa: E402

CELL = "deepseek-llm-7b-8L-s70.longctx-c12"


def test_sound_run_is_correct(capsys):
    assert R.execute(CELL, 2**31 + 12345, 1.0, False, True) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["compilations_in_window"] == 0
    assert line["attempted"] > 0 and line["failed"] == 0


def test_each_round_of_clients_draws_the_same_lengths():
    _, _, _, mix, _ = R.resolve(CELL)
    assert mix["pool"] == mix["clients"]
    stream = T.request_stream(mix, 7, 1000)
    rounds = []
    for _ in range(3):
        reqs = [next(stream) for _ in range(mix["clients"])]
        rounds.append((sorted(p.size for p, _ in reqs),
                       sorted(n for _, n in reqs)))
    assert rounds[0] == rounds[1] == rounds[2]
    prompts, outputs = np.array(rounds[0][0]), np.array(rounds[0][1])
    assert prompts.min() >= 2048 and prompts.max() <= 3584
    assert outputs.min() >= 64 and outputs.max() <= 256
