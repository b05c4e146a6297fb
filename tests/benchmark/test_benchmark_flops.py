"""The families' counts (``benchmark/counts/<reference>.py``) against hand
counts, and ``benchmark/flops.py`` finding them by name."""

import pytest

from _tiny import ROOT, load
from benchmark import flops
from benchmark.counts import gpt2, resnet50


def test_resnet50_forward_is_4_1_gmac():
    cfg = load(ROOT, "benchmark", "configs", "resnet50_v15.json")
    macs = resnet50.forward_macs(cfg)
    # He et al. quote 3.8 GFLOPs (multiply-adds) for the v1 network; v1.5
    # moves the stride into the 3x3, which adds ~0.3 G: 4.09 G at 224x224
    assert macs == pytest.approx(4.09e9, rel=0.01)
    by_hand_stem = 112 * 112 * 7 * 7 * 3 * 64
    by_hand_fc = 2048 * 1001
    assert macs > by_hand_stem + by_hand_fc
    assert resnet50.train_flops_per_example(cfg) == 6 * macs


def test_resnet_block_by_hand():
    cfg = {"image_size": 8, "num_filters": 4, "stage_sizes": [1],
           "num_classes": 3}
    # stem 4x4 out, pool 2x2; block: 1x1 (4->4), 3x3 (4->4), 1x1 (4->16),
    # projection 1x1 (4->16), all at 2x2; classifier 16 -> 3
    want = 4 * 4 * 49 * 3 * 4 + 4 * (16 + 9 * 16 + 64 + 64) + 16 * 3
    assert resnet50.forward_macs(cfg) == want


def test_gpt2_medium_is_6n_per_token_plus_attention():
    cfg = load(ROOT, "benchmark", "configs", "gpt2_medium.json")
    n = gpt2.matmul_params(cfg)
    by_hand = 24 * (1024 * 3072 + 1024 * 1024 + 2 * 1024 * 4096) \
        + 50257 * 1024
    assert n == by_hand
    seq = 1024
    attention = 24 * 2 * (seq * (seq + 1) // 2) * 1024
    assert gpt2.train_flops_per_example(cfg) == 6 * (seq * n + attention)
    # attention is a small share at 1024 positions: 6 N T dominates
    assert 6 * seq * n / gpt2.train_flops_per_example(cfg) > 0.9


def test_by_reference_family():
    cfg = load(ROOT, "benchmark", "configs", "gpt2_medium.json")
    assert flops.train_flops_per_example(cfg) == gpt2.train_flops_per_example(cfg)
    cfg = load(ROOT, "benchmark", "configs", "resnet50_v15.json")
    assert flops.infer_flops_per_example(cfg) * 3 == \
        flops.train_flops_per_example(cfg)


def test_a_family_is_found_by_its_name_alone():
    """No table: a ``reference`` is the name of a file under ``counts/``, and
    one that is not there is an error, not a default."""
    with pytest.raises(ImportError):
        flops.train_flops_per_example({"reference": "no_such_family"})
    # the two families that are here name no scope yet
    for name in ("gpt2_medium", "resnet50_v15"):
        assert flops.kernels(load(ROOT, "benchmark", "configs",
                                  name + ".json")) == {}
