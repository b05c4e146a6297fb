"""Each plain reference against the system's own model at a tiny size on the
CPU, and the control: the same comparison with the reference computed in fp8
in the program's place must come out as not correct."""

import importlib

import numpy as np
import pytest

import _tiny
from benchmark import correctness

# {configuration: cell}: every family's tiny training cell, a new family's as
# soon as its fragment is under tiny/manifest.d (gpt2_tiny, resnet_tiny today)
CELLS = _tiny.training_cells()
SEEDS = (1, 2, 2147483659)


def _limits(name):
    return _tiny.load(_tiny.TINY, "correctness", CELLS[name] + ".json")[
        "limits"]


@pytest.fixture(scope="module")
def readings():
    """{(config, seed): (reference, program numbers, control numbers)}, the
    program under the configuration's own bfloat16 policy."""
    out = {}
    for name in CELLS:
        cfg = _tiny.config(name)
        ref = importlib.import_module("benchmark.references."
                                      + cfg["reference"])
        for seed in SEEDS:
            rows = _tiny.batches(cfg, seed)
            want = ref.train_steps(cfg, seed, rows)
            good = correctness.training_numbers(
                _tiny.program_first_steps(cfg, seed, rows), want)
            bad = correctness.training_numbers(
                ref.train_steps(cfg, seed, rows, precision="fp8"), want)
            out[name, seed] = (want, good, bad)
    return out


@pytest.mark.parametrize("name", sorted(CELLS))
@pytest.mark.parametrize("seed", SEEDS)
def test_program_agrees_with_reference(readings, name, seed):
    _, good, _ = readings[name, seed]
    rows = correctness.judge(good, _limits(name))
    assert rows and all(ok for _, _, _, ok in rows), rows


@pytest.mark.parametrize("name", sorted(CELLS))
@pytest.mark.parametrize("seed", SEEDS)
def test_fp8_control_is_not_correct(readings, name, seed):
    _, good, bad = readings[name, seed]
    rows = correctness.judge(bad, _limits(name))
    assert not all(ok for _, _, _, ok in rows), rows
    # and by the number that is there to separate precisions, with room
    assert bad["grad_rel_diff"] > 3 * good["grad_rel_diff"]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_float32_program_matches_reference_closely(name):
    """With the program's dtype set to float32 the two are the same
    mathematics: the mapping of weights, the loss and the optimizer agree."""
    cfg = dict(_tiny.config(name), dtype="float32")
    ref = importlib.import_module("benchmark.references." + cfg["reference"])
    rows = _tiny.batches(cfg, 4, steps=1)
    want = ref.train_steps(cfg, 4, rows)
    got = _tiny.program_first_steps(cfg, 4, rows)
    numbers = correctness.training_numbers(got, want)
    # how closely is the family's own to say (the tiny configuration's
    # ``float32_agreement``; 1e-4 where it says nothing)
    held = cfg.get("float32_agreement", {})
    assert numbers["loss_gap"] < held.get("loss_gap", 1e-4)
    assert numbers["grad_rel_diff"] < held.get("grad_rel_diff", 1e-4)


def test_resnet_inference_reference_and_control():
    """Serving's comparison: the export's model in evaluation mode against
    ``predict``; fp8 in its place is several times farther."""
    import jax.numpy as jnp

    from benchmark import generate
    from benchmark.adapters import resnet50 as adapter
    from benchmark.references import resnet50 as ref

    cfg = _tiny.config("resnet_tiny")
    traffic = {"pool_images": 6}
    pool = generate.image_pool(3, traffic, cfg["image_size"])
    built = adapter.build(cfg, 3)
    logits = built["model"].apply(
        {"params": built["params"], "batch_stats": built["extra"]},
        jnp.asarray(pool), train=False)
    want = ref.predict(cfg, 3, pool, block=4)
    good = correctness.serving_numbers([np.asarray(logits)], [want])
    bad = correctness.serving_numbers(
        [ref.predict(cfg, 3, pool, precision="fp8", block=4)], [want])
    assert good["logit_rel_rms"] < 0.05
    assert bad["logit_rel_rms"] > 3 * good["logit_rel_rms"]


def test_norm_gap_is_by_the_worst_leaf_against_the_median_floor():
    ref = {"a": 1.0, "b": 2.0, "tiny": 1e-6}
    got = {"a": 1.1, "b": 2.0, "tiny": 3e-6}
    # the all-but-zero leaf is measured against the median leaf (1.0)
    assert correctness.norm_gap(got, ref) == pytest.approx(0.1)
