"""benchmark/flops.py against hand counts."""

import pytest

from _tiny import ROOT, load
from benchmark import flops


def test_resnet50_forward_is_4_1_gmac():
    cfg = load(ROOT, "benchmark", "configs", "resnet50_v15.json")
    macs = flops.resnet50_forward_macs(cfg)
    # He et al. quote 3.8 GFLOPs (multiply-adds) for the v1 network; v1.5
    # moves the stride into the 3x3, which adds ~0.3 G: 4.09 G at 224x224
    assert macs == pytest.approx(4.09e9, rel=0.01)
    by_hand_stem = 112 * 112 * 7 * 7 * 3 * 64
    by_hand_fc = 2048 * 1001
    assert macs > by_hand_stem + by_hand_fc
    assert flops.resnet50_train_flops(cfg) == 6 * macs


def test_resnet_block_by_hand():
    cfg = {"image_size": 8, "num_filters": 4, "stage_sizes": [1],
           "num_classes": 3}
    # stem 4x4 out, pool 2x2; block: 1x1 (4->4), 3x3 (4->4), 1x1 (4->16),
    # projection 1x1 (4->16), all at 2x2; classifier 16 -> 3
    want = 4 * 4 * 49 * 3 * 4 + 4 * (16 + 9 * 16 + 64 + 64) + 16 * 3
    assert flops.resnet50_forward_macs(cfg) == want


def test_gpt2_medium_is_6n_per_token_plus_attention():
    cfg = load(ROOT, "benchmark", "configs", "gpt2_medium.json")
    n = flops.gpt2_matmul_params(cfg)
    by_hand = 24 * (1024 * 3072 + 1024 * 1024 + 2 * 1024 * 4096) \
        + 50257 * 1024
    assert n == by_hand
    seq = 1024
    attention = 24 * 2 * (seq * (seq + 1) // 2) * 1024
    assert flops.gpt2_train_flops(cfg) == 6 * (seq * n + attention)
    # attention is a small share at 1024 positions: 6 N T dominates
    assert 6 * seq * n / flops.gpt2_train_flops(cfg) > 0.9


def test_by_reference_family():
    cfg = load(ROOT, "benchmark", "configs", "gpt2_medium.json")
    assert flops.train_flops_per_example(cfg) == flops.gpt2_train_flops(cfg)
    cfg = load(ROOT, "benchmark", "configs", "resnet50_v15.json")
    assert flops.infer_flops_per_example(cfg) * 3 == \
        flops.train_flops_per_example(cfg)
