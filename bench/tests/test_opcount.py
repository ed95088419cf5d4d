"""FLOP and byte counts against hand counts."""
import json
from pathlib import Path

import pytest

from bench.opcount import attention, conv2d, lm, matmul, resnet

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_conv2d_hand_count():
    # 3x3 stride-1 SAME conv, 2 images of 56x56x64 -> 56x56x128
    f, b = conv2d.conv2d(2, 56, 56, 64, 128, 3, 1)
    assert f == 2 * 2 * 56 * 56 * 9 * 64 * 128
    assert b == 2 * (2 * 56 * 56 * 64 + 9 * 64 * 128 + 2 * 56 * 56 * 128)
    # stride 2 halves each output side (rounding up)
    f2, _ = conv2d.conv2d(1, 7, 7, 8, 8, 3, 2)
    assert f2 == 2 * 4 * 4 * 9 * 8 * 8


def test_decode_attention_hand_count():
    # two rows of 100 and 300 keys, 24 heads over 8 KV heads of 128
    f, b = attention.decode_attention([100, 300], 24, 8, 128)
    assert f == 4 * 24 * 128 * 400
    assert b == 2 * (2 * 8 * 128 * 400 + 2 * 2 * 24 * 128)


def test_matmul_hand_count():
    assert matmul.matmul(16, 3072, 8192) == (2 * 16 * 3072 * 8192,
                                             2 * (16 * 3072 + 3072 * 8192
                                                  + 16 * 8192))


def test_resnet34_counts():
    c = json.loads((CONFIGS / "resnet34.json").read_text())
    convs = resnet.convs(c)
    assert len(convs) == 36            # 33 3x3/7x7 layers + 3 projections
    assert convs[0] == (224, 224, 3, 64, 7, 2)
    assert resnet.flops_per_image(c) == pytest.approx(7.33e9, rel=1e-3)


def test_phi4mini_counts():
    c = json.loads((CONFIGS / "phi4mini.json").read_text())
    emb = c["vocab_size"] * c["hidden_size"]
    assert lm.matmul_params(c) + emb == pytest.approx(3.836e9, rel=1e-3)
    # one request of 2 prompt tokens and 2 generated: 3 token passes
    f = lm.model_flops(c, [(2, 2)])
    d, H, nl = c["hidden_size"], c["num_attention_heads"], c["num_hidden_layers"]
    att = 4 * H * (d // H) * nl * (1 + 2 + 3)
    assert f == pytest.approx(3 * 2 * lm.matmul_params(c)
                              + 2 * 2 * d * c["vocab_size"] + att)
    assert list(lm.decode_rows([(5, 3)])) == [6, 7]
