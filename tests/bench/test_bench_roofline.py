"""Peaks table, model FLOP count and the share check."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from bench import roofline  # noqa: E402


def test_v5e_peaks_and_unknown_kind():
    p = roofline.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("cpu")


def test_qwen2_flops_by_hand():
    cfg = json.load(open(os.path.join(REPO, "bench/configs/qwen2-0.5b.json")))
    # per layer: wq 896x896, wk and wv 896x128 each, wo 896x896, three
    # 896x4864 MLP matrices; plus the tied LM head 151936x896 once
    per_layer = 896 * 896 * 2 + 896 * 128 * 2 + 3 * 896 * 4864
    assert per_layer == 14_909_440
    params = 24 * per_layer + 151936 * 896
    assert params == 493_961_216
    assert roofline.dense_lm_matmul_params(cfg) == params
    tokens = 512 * 8
    attn = 12 * 24 * 512 * 896
    want = tokens * (6 * params + attn)
    assert want == pytest.approx(1.26807e13, rel=1e-5)
    assert roofline.dense_lm_train_flops(cfg, 512, tokens) == want


def test_share_refuses_above_105():
    assert roofline.share(98.5, 100.0) == pytest.approx(98.5)
    assert roofline.share(105.0, 100.0) == pytest.approx(105.0)
    with pytest.raises(roofline.ShareTooHigh):
        roofline.share(106.0, 100.0)
