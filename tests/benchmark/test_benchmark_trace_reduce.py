"""The trace reduction on hand-made traces and on a recorded one."""

import json
import os

import pytest

from _tiny import ROOT
from benchmark import trace_reduce

MS = 1000000


def _plane(name, **lines):
    return {"name": name, "lines": [{"name": k.replace("_", " "), "events": v}
                                    for k, v in lines.items()]}


@pytest.fixture
def two_devices():
    """Two devices over a 100 ms window.  Device 0: compute 0-30 and 50-60,
    an all-reduce 25-45 (5 hidden, 15 exposed).  Device 1: compute 0-40, an
    all-gather 40-50 (all exposed).  The host: ``transform`` 28-52,
    ``on_steps`` 58-100 inside ``fit_feed`` 0-100."""
    host = _plane("/host:CPU", python=[
        ["perfbench/window", 0, 100 * MS],
        ["perfbench/fit_feed", 0, 100 * MS],
        ["perfbench/transform", 28 * MS, 24 * MS],
        ["perfbench/on_steps", 58 * MS, 42 * MS],
        ["something else", 0, 100 * MS]])
    d0 = _plane("/device:TPU:0", XLA_Ops=[
        ["fusion.1", 0, 30 * MS], ["all-reduce.3", 25 * MS, 20 * MS],
        ["fusion.2", 50 * MS, 10 * MS]], Steps=[["0", 0, 60 * MS]])
    d1 = _plane("/device:TPU:1", XLA_Ops=[
        ["fusion.1", 0, 40 * MS], ["%all-gather-start.7", 40 * MS, 10 * MS]])
    return [host, d0, d1]


def test_busy_union_and_window(two_devices):
    r = trace_reduce.reduce(two_devices)
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(0.100)
    # device 0 busy 0-45 and 50-60 = 55; device 1 busy 0-50 = 50
    assert r["busy_s"] == pytest.approx((0.055 + 0.050) / 2)


def test_collective_exposure(two_devices):
    r = trace_reduce.reduce(two_devices)
    assert r["collective_s"] == pytest.approx((0.020 + 0.010) / 2)
    assert r["collective_exposed_s"] == pytest.approx((0.015 + 0.010) / 2)


def test_top_operations(two_devices):
    r = trace_reduce.reduce(two_devices)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx((0.030 + 0.040) / 2)
    assert ops["all-reduce.3"] == pytest.approx(0.010)
    assert r["device_ops"][0][0] == "fusion.1"


def test_gaps_are_labelled_by_the_benchmarks_spans(two_devices):
    r = trace_reduce.reduce(two_devices)
    gaps = r["idle_gaps"]
    # longest: device 1 idle 50-100, mostly under on_steps (58-100)
    assert gaps[0] == ["perfbench/on_steps", pytest.approx(0.050)]
    assert ["perfbench/on_steps", pytest.approx(0.040)] in gaps
    # device 0 idle 45-50 lies wholly under transform (the innermost)
    assert ["perfbench/transform", pytest.approx(0.005)] in gaps
    assert len(gaps) <= 5


def test_no_device_plane_is_nothing_to_read():
    assert trace_reduce.reduce([_plane("/host:CPU", t=[["x", 0, 5]])]) is None


def test_without_a_window_span_the_operations_bound_it():
    d0 = _plane("/device:TPU:0", XLA_Ops=[["a", 10 * MS, 10 * MS],
                                          ["b", 40 * MS, 10 * MS]])
    r = trace_reduce.reduce([d0])
    assert r["window_s"] == pytest.approx(0.040)
    assert r["busy_s"] == pytest.approx(0.020)
    assert r["idle_gaps"] == [["no benchmark span", pytest.approx(0.020)]]


@pytest.mark.parametrize("a,b,want", [
    ([[0, 10]], [[2, 4], [6, 8]], [[0, 2], [4, 6], [8, 10]]),
    ([[0, 10], [20, 30]], [[5, 25]], [[0, 5], [25, 30]]),
    ([[0, 10]], [], [[0, 10]]),
    ([[0, 10]], [[0, 10]], []),
])
def test_interval_subtraction(a, b, want):
    assert trace_reduce._subtract(a, b) == want


def test_recorded_chip_trace():
    """The first 8 ms of the traced window of a GPT-2-medium run on the v5e
    (my chip run, PR 23; ``trace_reduce.dump_head``): the step's first
    operations, the window span and one ``on_steps`` span of the benchmark."""
    import gzip

    path = os.path.join(ROOT, "benchmark", "fixtures",
                        "gpt2m_v5e_trace.json.gz")
    with gzip.open(path, "rt") as f:
        planes = json.load(f)
    r = trace_reduce.reduce(planes)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.008)
    # as read when it was recorded: 5.56 ms of the 8 busy (a 2.44 ms gap
    # under the infeed thread's transform), nothing collective
    assert r["busy_s"] == pytest.approx(0.005559, abs=1e-5)
    assert r["idle_gaps"][0] == ["perfbench/transform",
                                 pytest.approx(0.002441, abs=1e-5)]
    assert r["collective_s"] == 0
    assert r["device_ops"][0][1] > 0
    assert all(" = " in name and "{" not in name
               for name, _ in r["device_ops"])
    assert "perfbench/on_steps" in r["spans"]
    assert sum(t for _, t in r["idle_gaps"]) <= 0.008 - r["busy_s"] + 1e-9
