"""Required-work counters against hand sums."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from lib import work as W  # noqa: E402


def _conf(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_packed_projection_by_hand():
    # DeepSeek-7B ffn/wi: 11008 x 4096 at 70% pruning of 128x128 tiles
    nb = (11008 // 128) * (4096 // 128)              # 2752 tiles
    nnz = round(nb * 0.3)                            # 826
    flops, nbytes = W.packed_matmul(16, 4096, 11008, nnz, (128, 128))
    assert flops == 2 * 16 * 826 * 128 * 128
    assert nbytes == 826 * 128 * 128 * 2 + 16 * (4096 + 11008) * 2
    # memory-bound at 16 rows: least time is bytes over bandwidth
    assert W.least_s((flops, nbytes), 197e12, 819e9) == nbytes / 819e9


def test_decode_attention_by_hand():
    flops, nbytes = W.decode_attention(1000, 32, 128, 32)
    assert flops == 4 * 32 * 128 * 1000
    assert nbytes == 2 * 1000 * 32 * 128 * 2         # K and V, bf16


def test_attention_pairs():
    f_full, _ = W.attention(4, 4, 1, 1, 1, causal=False)
    f_causal, _ = W.attention(4, 4, 1, 1, 1, causal=True)
    assert f_full == 4 * 16 and f_causal == 4 * 10


def test_lm_step_log_reconstruction():
    """Two requests: one prefilled this step with 3 decode tokens after it,
    one resident that decoded 2: the window ran 3 sub-steps, the first two
    with both requests, the third with one."""
    from families import lm
    conf = _conf("deepseek-llm-7b-8L-s70")
    conf = dict(conf, name="x")
    log = [[(100, 1, 3, True), (50, 7, 2, False)]]
    w = lm.work(conf, {}, log)
    assert len(w["steps"]) == 1 + 3                  # one prefill, 3 steps
    assert len(w["decode_attn"]) == 3
    L = conf["num_hidden_layers"]
    ctx0 = [100 + 1, 50 + 7]
    da = [W.decode_attention(c, 32, 128, 32) for c in ctx0]
    assert w["decode_attn"][0] == (L, (da[0][0] + da[1][0],
                                       da[0][1] + da[1][1]))
    assert w["decode_attn"][2] == (L, W.decode_attention(100 + 1 + 2, 32,
                                                          128, 32))
    # five packs per layer per sub-step and per prefill
    assert len(w["sparse_matmul"]) == 5 * 4


def test_bert_request_work_by_hand():
    from families import bert
    conf = dict(_conf("bert-base-s80"), name="x")
    w = bert.work(conf, n=2, b=1, s=384)
    # 768x768: 36 tiles keep 7; 3072x768: 144 tiles keep 29
    tiles = 3 * 7 + 7 + 29 + 29
    per_layer = 2 * 384 * tiles * 128 * 128 + 4 * 384 * 384 * 768
    head = 2 * 384 * 768 * 768 + 2 * 384 * 768 * 30522
    assert w["flops"] == pytest.approx(2 * (12 * per_layer + head))
    assert sum(n for n, _ in w["sparse_matmul"]) == 2 * 12 * 4
