"""The one generator: the same seed gives the same inputs, and every seed the
same set of sizes and arrivals in another order."""

import importlib.util
import os
import sys

import numpy as np
import pytest

from _tiny import ROOT
from benchmark import generate

MIX = {"rate_rps": 48.0, "size_weights": {"1": 8, "2": 4, "4": 2, "8": 1},
       "sample_fraction": 0.1, "sample_min": 24, "pool_images": 4}
BIG = 2 ** 31 + 12345


def test_arrivals_are_the_same_multiset_for_every_seed():
    a = generate.arrivals(1, MIX, 20.0)
    b = generate.arrivals(BIG, MIX, 20.0)
    assert len(a) == len(b) == 960
    assert sorted(s for _, s in a) == sorted(s for _, s in b)
    every = set(np.round(generate.poisson_gaps(960, 48.0, 20.0), 9))
    for sched in (a, b):
        assert set(np.round(np.diff([t for t, _ in sched]), 9)) <= every
    assert [s for _, s in a] != [s for _, s in b]
    assert a == generate.arrivals(1, MIX, 20.0)
    assert a[0][0] == 0.0 and a[-1][0] < 20.0


def test_sizes_follow_the_weights_exactly():
    sizes = [s for _, s in generate.arrivals(3, MIX, 20.0)]
    assert {n: sizes.count(n) for n in (1, 2, 4, 8)} == \
        {1: 512, 2: 256, 4: 128, 8: 64}


def test_rate_override_and_mean_gap():
    sched = generate.arrivals(3, MIX, 10.0, rate=30.0)
    assert len(sched) == 300
    assert np.mean(np.diff([t for t, _ in sched])) == pytest.approx(
        10.0 / 300, rel=0.02)


def test_sampled_requests_hold_the_largest():
    sched = generate.arrivals(7, MIX, 20.0)
    chosen = generate.sample_requests(7, sched, MIX)
    assert 96 <= len(chosen) <= 97
    assert any(sched[i][1] == 8 for i in chosen)
    assert chosen == generate.sample_requests(7, sched, MIX)
    assert chosen != generate.sample_requests(8, sched, MIX)


@pytest.mark.parametrize("seed", [0, 5, BIG])
def test_rows_can_be_made_again_alone(seed):
    traffic = {"store_px": 12, "image_size": 8, "num_classes": 11,
               "seq_len": 16, "vocab_size": 97}
    table = [generate.image_row(seed, i, traffic) for i in range(6)]
    again = generate.image_row(seed, 4, traffic)
    assert np.array_equal(table[4][0], again[0]) and table[4][1:] == again[1:]
    assert 0 <= again[3] <= 4 and 0 <= again[4] <= 4 and 1 <= again[1] <= 10
    assert not np.array_equal(table[0][0], table[1][0])
    tokens = generate.token_rows(seed, traffic, 0, 6)
    assert np.array_equal(tokens[3], generate.token_rows(
        seed, traffic, 3, 1)[0])
    assert tokens[:, 0].tolist() == list(range(6))
    assert tokens[:, 1:].max() < 97


@pytest.mark.parametrize("name", ["gpt2_tiny", "resnet_tiny"])
def test_shards_hold_whole_rows_of_either_adapter(name, tmp_path):
    """Either transport carries any adapter's rows: the FILES transport's
    shards are whole rows of the adapter's ``row_dtype``, and its reader
    yields what ``make_row`` made."""
    import importlib

    import _tiny
    from benchmark.drivers import train_feed

    cfg = _tiny.config(name)
    adapter = importlib.import_module("benchmark.adapters." + cfg["adapter"])
    paths = train_feed._write_shards(adapter, cfg, 3, {"rows": 8, "shards": 2},
                                     str(tmp_path))
    dtype = adapter.row_dtype(cfg)
    rows = [r for p in paths for r in train_feed._shard_reader(dtype)(p)]
    assert [r["index"] for r in rows] == list(range(8))
    made = dict(zip(dtype.names, adapter.make_row(cfg, 3, 5)))
    assert all(np.array_equal(rows[5][k], made[k]) for k in dtype.names)
    batch, tag = adapter.to_batch(
        {k: np.stack([np.asarray(r[k]) for r in rows]) for k in dtype.names})
    again, tag2 = train_feed._remake(adapter, cfg, 3, range(8))
    assert tag.tolist() == tag2.tolist()
    assert all(np.array_equal(batch[k], again[k]) for k in batch)
    assert "index" not in batch


def test_request_images_are_a_seeded_draw_from_the_pool():
    pool = generate.image_pool(2, MIX, 8)
    assert pool.shape == (4, 8, 8, 3) and pool.dtype == np.float32
    x = generate.request_images(2, 17, 8, pool)
    assert x.shape == (8, 8, 8, 3)
    assert np.array_equal(x, generate.request_images(2, 17, 8, pool))
    assert all(any(np.array_equal(img, p) for p in pool) for img in x)


def _reader(kind, name):
    directory = os.path.join(ROOT, "benchmark", kind)
    if directory not in sys.path:
        sys.path.insert(0, directory)
    spec = importlib.util.spec_from_file_location(
        "r", os.path.join(ROOT, "benchmark", kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_histogram_percentile_interpolates_inside_the_bucket():
    hist = _reader("layer_metrics", "_hist")
    delta = {"h_le_1000": 10, "h_le_2500": 60, "h_le_5000": 100,
             "h_count": 100}
    assert hist.percentile(delta, "h", 0.50) == pytest.approx(2200.0)
    assert hist.percentile(delta, "h", 0.95) == pytest.approx(4687.5)
    assert hist.percentile({}, "h", 0.5) is None


TRAIN = {"window": {"seconds": 10.0, "steps": 50, "examples": 12800,
                    "chips": 1, "setup_s": 40.0,
                    "compiles": {"backend_compiles": 0, "cache_hits": 0,
                                 "cache_misses": 0},
                    "delta": {"trainer": {"dispatch_count": 50,
                                          "dispatch_gap_us": 9000000,
                                          "goodput_infeed_starved_us": 8e6},
                              "infeed": {"infeed_batches": 50,
                                         "infeed_assembly_us": 14000000,
                                         "infeed_put_us": 1000000},
                              "feed": {"feed_items": 12800}}},
         # ten profiled steps after the window, 0.1 s busy each
         "trace": {"busy_s": 1.0, "window_s": 4.0, "steps": 10},
         "process_compiles": {"cache_misses": 0},
         "device": {"peaks": {"bf16_flops_per_s": 197e12}},
         "model": {"flops_per_example": 24.5e9, "batch_size": 256}}
SERVE = {"window": {"seconds": 20.0, "good": 950, "requests": 960,
                    "latency_ms": {"p95": 160.0}, "late_ms_p95": 1.0,
                    "setup_s": 36.0,
                    "delta": {"replica": {"serving_rows": 2000,
                                          "serving_batches": 500}}},
         "trace": {"busy_s": 0.2, "window_s": 4.0},
         "model": {"max_batch": 8}}


@pytest.mark.parametrize("kind,name,report,want", [
    ("end_to_end", "train_examples_per_s", TRAIN, 1280.0),
    ("end_to_end", "setup_s", TRAIN, 40.0),
    ("end_to_end", "serve_p95_ms", SERVE, 160.0),
    ("end_to_end", "serve_goodput_rps", SERVE, 47.5),
    ("end_to_end", "train_examples_per_s", SERVE, None),
    ("end_to_end", "serve_p95_ms", TRAIN, None),
    ("layer_metrics", "feed_rows_per_s", TRAIN, 1280.0),
    ("layer_metrics", "infeed_host_ms_per_batch", TRAIN, 300.0),
    ("layer_metrics", "infeed_starved_pct", TRAIN, 80.0),
    ("layer_metrics", "dispatch_gap_ms.train", TRAIN, 180.0),
    ("layer_metrics", "compiles_in_window.train", TRAIN, 0.0),
    # 0.1 s busy a step x 5 steps a second of the (unprofiled) window
    ("layer_metrics", "device_idle_pct.train", TRAIN, 50.0),
    ("layer_metrics", "device_idle_pct.serve", SERVE, 95.0),
    ("layer_metrics", "device_idle_pct.serve", TRAIN, None),
    ("layer_metrics", "serve_batch_fill_pct", SERVE, 50.0),
    ("layer_metrics", "loadgen_late_ms_p95", SERVE, 1.0),
    ("layer_metrics", "compile_cache_misses", TRAIN, 0.0),
    ("layer_metrics", "feed_rows_per_s", SERVE, None),
    # 24.5 GFLOP x 256 examples x 10 steps / 197 TFLOP/s = 0.3184 s ideal
    # over 1 s busy
    ("layer_metrics", "step_roofline_pct.train", TRAIN, 31.84),
])
def test_readers(kind, name, report, want):
    got = _reader(kind, name).read(report)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-3)
