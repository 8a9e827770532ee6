"""Work counts and peaks, against hand counts."""
import json
from pathlib import Path

import numpy as np
import pytest

from harness import flops
from harness.peaks import UnknownDevice, peaks

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_smollm_linear_flops():
    c = config("smollm-360m")
    # q 960x960, k and v 960x320, o 960x960, MLP 3 x 960x2560
    assert 2 * flops.linear_params_per_layer(c) == 19_660_800
    # 32 layers plus the tied head, 2 x 960 x 49152 = 94.4 M
    assert flops.linear_flops_per_token(c) == 19_660_800 * 32 + 94_371_840
    assert flops.linear_flops_per_token(c) == pytest.approx(723.5e6, rel=1e-4)


def test_mistral_large_tp8_share_linear_flops():
    # one chip's share of Mistral-Large-2 at 8-way tensor parallelism:
    # 12 q / 1 kv heads of 128, MLP 3584, 4 layers, a 4096-row vocabulary
    c = {"hidden_size": 12288, "head_dim": 128, "num_attention_heads": 12,
         "num_key_value_heads": 1, "intermediate_size": 3584,
         "num_hidden_layers": 4, "vocab_size": 4096}
    assert flops.linear_params_per_layer(c) == 173_015_040
    assert flops.linear_flops_per_token(c) == pytest.approx(1.485e9, rel=1e-3)


def test_mistral_7b_linear_flops():
    c = config("mistral-7b")
    # q and o 4096x4096, k and v 4096x1024, MLP 3 x 4096x14336
    assert flops.linear_params_per_layer(c) == 218_103_808
    assert flops.linear_flops_per_token(c) == \
        2 * 218_103_808 * c["num_hidden_layers"] + 2 * 4096 * 32768


def test_live_pairs_hand_layout():
    # row 0: a 3-token document, padding, a 2-token document;
    # row 1: one 4-token document.  3*4/2 + 2*3/2 + 4*5/2 = 6 + 3 + 10
    seg = np.array([[1, 1, 1, 0, 2, 2, 0, 0], [3, 3, 3, 3, 0, 0, 0, 0]])
    pos = np.array([[0, 1, 2, 0, 0, 1, 0, 0], [0, 1, 2, 3, 0, 0, 0, 0]])
    assert flops.live_pairs(seg, pos) == 19
    assert flops.live_pairs(np.zeros((2, 8), int), pos) == 0


def test_ca_call_work_counts_pairs_and_bytes():
    c = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8}
    seg = np.array([[1, 1, 1, 0]])
    pos = np.array([[0, 1, 2, 0]])
    fwd = flops.ca_call_work(c, seg, pos, backward=False)
    bwd = flops.ca_call_work(c, seg, pos, backward=True)
    assert fwd["flops"] == 4 * 6 * 4 * 8
    assert bwd["flops"] == 2 * fwd["flops"]
    q, kv, lse = 3 * 4 * 8 * 2, 3 * 2 * 8 * 2, 3 * 4 * 4
    assert fwd["bytes"] == (q + 2 * kv) + (q + lse)
    assert bwd["bytes"] == (3 * q + 2 * kv + lse) + (q + 2 * kv)


def test_step_flops_is_three_forwards():
    c = config("smollm-360m")
    seg = np.array([[1, 1, 0, 0]])
    pos = np.array([[0, 1, 0, 0]])
    fwd = 2 * flops.linear_flops_per_token(c) \
        + c["num_hidden_layers"] * flops.attention_fwd_flops(c, 3)
    assert flops.step_flops(c, {"segment_ids": seg, "positions": pos}) == \
        3 * fwd


def test_peaks_table():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(UnknownDevice):
        peaks("TPU v9 imaginary")
