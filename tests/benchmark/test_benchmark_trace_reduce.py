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


@pytest.fixture
def two_threads():
    """One device, idle 20-80 of a 100 ms window.  The step loop's thread:
    the benchmark's ``fit_feed`` 0-100 with the program's ``train/dispatch``
    22-70 inside; the infeed's thread: ``infeed/assemble`` 0-90 with
    ``feed/wait`` 10-85 and the benchmark's ``transform`` 86-89 inside."""
    host = {"name": "/host:CPU", "lines": [
        {"name": "main/1", "events": [
            ["perfbench/window", 0, 100 * MS],
            ["perfbench/fit_feed", 0, 100 * MS],
            ["tfos/train/dispatch", 22 * MS, 48 * MS],
            ["tfos/train/next_batch", 71 * MS, 8 * MS]]},
        {"name": "python3", "events": [
            ["tfos/infeed/assemble", 0, 90 * MS],
            ["tfos/feed/wait", 10 * MS, 75 * MS],
            ["perfbench/transform", 86 * MS, 3 * MS]]},
        {"name": "python3", "events": [["neither/prefix", 0, 100 * MS]]}]}
    d0 = _plane("/device:TPU:0", XLA_Ops=[["a", 0, 20 * MS],
                                          ["b", 80 * MS, 4 * MS],
                                          ["c", 92 * MS, 8 * MS]])
    return [host, d0]


def test_a_gap_is_labelled_by_the_innermost_span_of_each_thread(two_threads):
    """Either prefix, a thread each: what the program was doing, not only
    which of the benchmark's calls it was in."""
    gaps = trace_reduce.reduce(two_threads)["idle_gaps"]
    # 20-80: the infeed's thread waited for the feed all of it (60 of 60),
    # the step loop's was in dispatch for 48: the thread that covers more
    # comes first; fit_feed and assemble cover it too but are not innermost
    assert gaps[0] == ["tfos/feed/wait | tfos/train/dispatch",
                       pytest.approx(0.060)]
    # 84-92: on the infeed's thread feed/wait covers 1 of 8 and transform 3,
    # so the innermost span that covers half is assemble (6 of 8); on the
    # step loop's nothing lies inside fit_feed (8 of 8, so it comes first)
    assert gaps[1] == ["perfbench/fit_feed | tfos/infeed/assemble",
                       pytest.approx(0.008)]
    spans = trace_reduce.reduce(two_threads)["spans"]
    assert "tfos/feed/wait" in spans and "neither/prefix" not in spans


def test_two_lines_of_one_name_are_two_threads(two_threads):
    """The profiler names every Python thread's line ``python3``."""
    threads = {t for _, _, _, t in trace_reduce.host_spans(two_threads)}
    assert len(threads) == 2


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/jit(main)/transpose(jvp(TransformerLM))/block_0/"
     "Dense_0/dot_general", "TransformerLM/block_0/Dense_0"),
    ("jit(f)/transpose(jvp(other/inner))/add_any", "other/inner"),
    ("jit(f)/jvp(myscope)/dot_general", "myscope"),
    ("jit(f)/jit(main)/jvp(jit(inner))/vmap(expert)/mul", "expert"),
    ("jit(f)/jit(main)/mul", ""),
    ("x", ""),
    ("", ""),
    (None, ""),
])
def test_scope_of_an_op_name(op_name, scope):
    assert trace_reduce.scope_of(op_name) == scope


@pytest.fixture
def scoped():
    """Two devices, a 100 ms window.  Device 0: ``fusion.1`` of
    ``M/block_0/mlp`` 0-30 (forward) and ``fusion.2`` of the same scope
    60-70 (its backward pass), ``fusion.3`` of ``M/block_0/attn`` 30-50, a
    ``while.1`` of ``M/block_1`` 70-90 lying over its body's ``fusion.4`` of
    ``M/block_1/mlp`` 72-88, a copy with no origin 90-95.  Device 1: the
    same but for ``fusion.1``, 10 ms shorter."""
    where = {"fusion.1": "jit(step)/jit(main)/jvp(M)/block_0/mlp/dot_general",
             "fusion.2": "jit(step)/jit(main)/transpose(jvp(M))/block_0/mlp/"
                         "dot_general",
             "fusion.3": "jit(step)/jit(main)/jvp(M)/block_0/attn/exp",
             "while.1": "jit(step)/jit(main)/jvp(M)/block_1/while",
             "fusion.4": "jit(step)/jit(main)/jvp(M)/block_1/mlp/tanh"}

    def device(i, first):
        plane = _plane("/device:TPU:%d" % i, XLA_Ops=[
            ["fusion.1", 0, first * MS], ["fusion.3", 30 * MS, 20 * MS],
            ["fusion.2", 60 * MS, 10 * MS], ["while.1", 70 * MS, 20 * MS],
            ["fusion.4", 72 * MS, 16 * MS], ["copy.9", 90 * MS, 5 * MS]])
        return dict(plane, origins=where)

    host = _plane("/host:CPU", python=[["perfbench/window", 0, 100 * MS]])
    return [host, device(0, 30), device(1, 20)]


def test_device_time_by_scope(scoped):
    r = trace_reduce.reduce(scoped)
    by = r["by_scope"]
    assert by["M/block_0/mlp"] == pytest.approx((0.040 + 0.030) / 2)
    assert by["M/block_0/attn"] == pytest.approx(0.020)
    # an ancestor holds the union of what lies below it
    assert by["M/block_0"] == pytest.approx((0.060 + 0.050) / 2)
    # a loop's event lies over its body's: block_1 is 20, not 20 + 16
    assert by["M/block_1"] == pytest.approx(0.020)
    assert by["M/block_1/mlp"] == pytest.approx(0.016)
    assert by["M"] == pytest.approx((0.080 + 0.070) / 2)
    assert by[trace_reduce.NO_SCOPE] == pytest.approx(0.005)
    assert by["M"] + by[trace_reduce.NO_SCOPE] == pytest.approx(r["busy_s"])
    # the operations' names say where they came from
    assert r["device_ops"][0][0] == "fusion.1 @M/block_0/mlp"


def test_scope_depth_cuts_the_paths(scoped):
    by = trace_reduce.reduce(scoped, scope_depth=2)["by_scope"]
    assert set(by) == {"M", "M/block_0", "M/block_1", trace_reduce.NO_SCOPE}


def test_a_trace_without_origins_has_no_by_scope(two_devices):
    """Left out, not guessed: the readers then find nothing to read."""
    assert "by_scope" not in trace_reduce.reduce(two_devices)


def _pb(field, value):
    """One protobuf field on the wire: an int as a varint, bytes as a
    length-delimited field."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(field << 3 | 2) + varint(len(value)) + value


def test_origins_are_read_from_the_files_wire_format():
    """An XSpace by hand: a host plane; a device plane whose stat 26 is
    ``tf_op``, with an operation that carries it as a string, one that
    carries it as a reference to a stat's name, one that carries another
    stat only, and a line of events (skipped over, not decoded)."""
    def entry(key, message):
        return _pb(1, key) + _pb(2, message)

    def stat_meta(i, name):
        return _pb(5, entry(i, _pb(1, i) + _pb(2, name)))

    def event_meta(i, name, *stats):
        return _pb(4, entry(i, _pb(1, i) + _pb(2, name)
                            + b"".join(_pb(5, st) for st in stats)))

    long_line = "%fusion.1 = bf16[4,1024]{1,0:T(8,128)(2,1)} fusion(" \
        + "bf16[4,1024]{1,0} %p, " * 12 + "), kind=kLoop"
    assert len(long_line) > 300        # a two-byte length on the wire
    line = _pb(1, 1) + _pb(2, "XLA Ops") + b"".join(
        _pb(4, _pb(1, 7 + i % 3) + _pb(2, i * 1000) + _pb(3, 500)
            + b"\x11" + b"\0" * 8)             # a fixed64 field (a double)
        for i in range(50))
    device = (_pb(1, 2) + _pb(2, "/device:TPU:0") + _pb(3, line)
              + stat_meta(26, "tf_op") + stat_meta(3, "device_offset_ps")
              + stat_meta(40, "jit(f)/jvp(by_reference)/mul")
              + event_meta(7, long_line, _pb(1, 3) + _pb(3, 12345678901234),
                           _pb(1, 26) + _pb(5, "jit(f)/jvp(myscope)/dot"))
              + event_meta(8, "%fusion.2 = ...", _pb(1, 26) + _pb(7, 40))
              + event_meta(9, "%copy.3 = ...", _pb(1, 3) + _pb(3, 5)))
    host = _pb(1, 1) + _pb(2, "/host:CPU") + stat_meta(26, "tf_op") \
        + event_meta(1, "a host event", _pb(1, 26) + _pb(5, "host/op"))
    raw = _pb(1, host) + _pb(1, device) + _pb(2, "an error")
    assert trace_reduce.origins(raw) == {"/device:TPU:0": {
        long_line: "jit(f)/jvp(myscope)/dot",
        "%fusion.2 = ...": "jit(f)/jvp(by_reference)/mul"}}


def test_load_reads_a_real_profile_and_its_origins(tmp_path):
    """What this jax writes on the CPU: ``load`` gives planes, lines and
    events, and ``origins`` walks the same file without an error (a CPU
    trace has no device plane, so there is nothing to find)."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("tfos/train/dispatch"):
        with jax.named_scope("scoped"):
            jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    planes = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    assert any(name == "tfos/train/dispatch"
               for name, _, _, _ in trace_reduce.host_spans(planes))
    assert all(p["origins"] == {} for p in planes)
    assert trace_reduce.reduce(planes) is None


def test_no_device_plane_is_nothing_to_read():
    assert trace_reduce.reduce([_plane("/host:CPU", t=[["x", 0, 5]])]) is None


def test_without_a_window_span_the_operations_bound_it():
    d0 = _plane("/device:TPU:0", XLA_Ops=[["a", 10 * MS, 10 * MS],
                                          ["b", 40 * MS, 10 * MS]])
    r = trace_reduce.reduce([d0])
    assert r["window_s"] == pytest.approx(0.040)
    assert r["busy_s"] == pytest.approx(0.020)
    assert r["idle_gaps"] == [["no span", pytest.approx(0.020)]]


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
    (my chip run, PR 27; ``tools/record_fixture.py``): the step's first
    operations with where each came from, the window span, and the
    benchmark's and the program's host spans on two threads."""
    import gzip

    path = os.path.join(ROOT, "benchmark", "fixtures",
                        "gpt2m_v5e_trace.json.gz")
    with gzip.open(path, "rt") as f:
        planes = json.load(f)
    r = trace_reduce.reduce(planes)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.008)
    # as read when it was recorded: 6.21 ms of the 8 busy, a 1.79 ms gap
    # while the step loop's thread was in the program's dispatch, nothing
    # collective
    assert r["busy_s"] == pytest.approx(0.006210, abs=1e-5)
    assert r["idle_gaps"][0] == ["tfos/train/dispatch",
                                 pytest.approx(0.001789, abs=1e-5)]
    assert r["collective_s"] == 0
    assert r["device_ops"][0][1] > 0
    assert all(" = " in name and "{" not in name
               for name, _ in r["device_ops"])
    assert {"perfbench/on_steps", "tfos/train/dispatch",
            "tfos/infeed/queue_full"} <= set(r["spans"])
    assert sum(t for _, t in r["idle_gaps"]) <= 0.008 - r["busy_s"] + 1e-9
    # where the device's time went, by the model's own scopes: the first
    # three blocks' attention leads, and the top scope with the operations
    # that carry none is all of the busy time
    by = r["by_scope"]
    assert r["device_ops"][0][0].startswith(
        "fusion.36 @TransformerLM/block_0/Attention_0 = ")
    assert by["TransformerLM/block_0/Attention_0"] == pytest.approx(
        0.001414, abs=1e-5)
    assert by["TransformerLM/block_0/Attention_0"] > \
        0.7 * by["TransformerLM/block_0"]
    assert by["TransformerLM"] + by[trace_reduce.NO_SCOPE] == pytest.approx(
        r["busy_s"], rel=1e-3)
    assert max(by.values()) <= r["busy_s"]
