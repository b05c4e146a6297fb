"""The feed cycle's per-layer metrics: each reader on a hand-made report
(the value by hand; ``None``, never an exception, on anything missing, zero or
not a number), their ``per_layer`` entries held against the manifest's
contract, and the tiny rehearsals printing them in the cells that list them.

The entries are listed in ``benchmark/layer_metrics/feed_cycle.per_layer.json``
and, since PR 27, named by ``BENCHMARK.json`` as they stand and by the
rehearsal manifest in their tiny form (its first fragment,
``tiny/manifest.d/00_feed_cycle.json``); a metric has to be named by both at
once (``test_tiny_manifest_covers_the_real_one``), and the tests here hold
both to the list."""

import copy
import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

import _tiny
from _tiny import ROOT

sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from run import load_reader  # noqa: E402  (benchmark/run.py: never imports jax)

ENTRIES = _tiny.load(ROOT, "benchmark", "layer_metrics",
                     "feed_cycle.per_layer.json")
FEED_METRICS = ["feeder_ship_ms_per_krow", "feeder_pack_put_ms_per_krow",
                "feeder_replay_ms_per_krow", "feeder_drain_ms_per_krow",
                "feed_wait_ms_per_krow", "feed_read_ms_per_krow"]

REPORT = {"window": {"seconds": 10.0, "delta": {
    "feed": {"feed_items": 8000, "feed_wait_us": 7200000,
             "feed_read_us": 400000, "feed_assemble_us": 240000,
             "feed_away_us": 2160000,
             "feeder_items": 8192, "feeder_tasks": 8,
             "feeder_between_tasks_us": 3000000, "feeder_source_us": 276800,
             "feeder_pack_put_us": 2048000, "feeder_replay_us": 1024000,
             "feeder_drain_us": 4096000},
    "infeed": {"infeed_batches": 32, "infeed_put_us": 48000,
               "infeed_assembly_us": 9000000},
    "trainer": {"dispatch_count": 32}}}}

BY_HAND = {
    "feeder_ship_ms_per_krow": (3000000 + 276800) / 8192,     # 400.0
    "feeder_pack_put_ms_per_krow": 250.0,
    "feeder_replay_ms_per_krow": 125.0,
    "feeder_drain_ms_per_krow": 500.0,
    "feed_wait_ms_per_krow": 900.0,
    "feed_read_ms_per_krow": 80.0,
    "h2d_ms_per_batch": 1.5,
}

# metric -> (group, its time keys, its count key)
READS = {
    "feeder_ship_ms_per_krow": ("feed", ["feeder_between_tasks_us",
                                         "feeder_source_us"], "feeder_items"),
    "feeder_pack_put_ms_per_krow": ("feed", ["feeder_pack_put_us"],
                                    "feeder_items"),
    "feeder_replay_ms_per_krow": ("feed", ["feeder_replay_us"],
                                  "feeder_items"),
    "feeder_drain_ms_per_krow": ("feed", ["feeder_drain_us"], "feeder_items"),
    "feed_wait_ms_per_krow": ("feed", ["feed_wait_us"], "feed_items"),
    "feed_read_ms_per_krow": ("feed", ["feed_read_us", "feed_assemble_us"],
                              "feed_items"),
    "h2d_ms_per_batch": ("infeed", ["infeed_put_us"], "infeed_batches"),
}


def _tiny_entries():
    return [dict(e, workloads=[t for w in e["workloads"]
                               for t in ENTRIES["tiny_cells"][w]])
            for e in ENTRIES["per_layer"]]


@pytest.fixture(scope="module")
def manifest():
    return _tiny.load(ROOT, "BENCHMARK.json")


def _reader(manifest, name):
    return load_reader(manifest, "layer_metrics", name)


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_gives_the_value_worked_by_hand(manifest, name):
    assert _reader(manifest, name)(REPORT) == pytest.approx(BY_HAND[name])


def test_the_four_feeder_metrics_add_up_to_the_feeders_wall_time(manifest):
    """The phases are the feeder's whole wall time: a krow's four shares sum
    to the wall time a krow delivered."""
    feed = REPORT["window"]["delta"]["feed"]
    wall_us = sum(v for k, v in feed.items()
                  if k.startswith("feeder_") and k.endswith("_us"))
    total = sum(_reader(manifest, n)(REPORT) for n in FEED_METRICS[:4])
    assert total == pytest.approx(wall_us / feed["feeder_items"])


def _broken(name, how):
    group, times, count = READS[name]
    report = copy.deepcopy(REPORT)
    delta = report["window"]["delta"]
    if how == "no group":
        del delta[group]
    elif how == "group is None":
        delta[group] = None
    elif how == "no time key":
        del delta[group][times[-1]]
    elif how == "no count key":
        del delta[group][count]
    elif how == "zero count":
        delta[group][count] = 0
    elif how == "negative count":
        delta[group][count] = -5
    elif how == "a string":
        delta[group][times[0]] = "12"
    elif how == "count a string":
        delta[group][count] = "many"
    elif how == "None":
        delta[group][times[0]] = None
    elif how == "nan":
        delta[group][times[0]] = float("nan")
    elif how == "a bool":
        delta[group][count] = True
    elif how == "negative time":
        delta[group][times[0]] = -1
    elif how == "no delta":
        del report["window"]["delta"]
    elif how == "no window":
        report = {}
    return report


@pytest.mark.parametrize("how", [
    "no group", "group is None", "no time key", "no count key", "zero count",
    "negative count", "a string", "count a string", "None", "nan", "a bool",
    "negative time", "no delta", "no window"])
@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_finds_nothing_and_does_not_raise(manifest, name, how):
    assert _reader(manifest, name)(_broken(name, how)) is None


def test_a_program_without_the_counters_reports_none_of_the_feed_metrics(
        manifest):
    """The parent of the PR that added the counters: group ``feed`` holds
    what it always held."""
    report = copy.deepcopy(REPORT)
    report["window"]["delta"]["feed"] = {"feed_items": 8000,
                                         "feed_stall_secs": 7.2}
    for name in FEED_METRICS:
        assert _reader(manifest, name)(report) is None
    assert _reader(manifest, "h2d_ms_per_batch")(report) == 1.5


def test_entries_list_the_seven_where_they_can_be_read():
    by_name = {m["name"]: m for m in ENTRIES["per_layer"]}
    assert list(by_name) == FEED_METRICS + ["h2d_ms_per_batch"]
    for name in FEED_METRICS:
        assert by_name[name]["workloads"] == ["resnet50_train_spark"]
        assert by_name[name]["unit"] == "ms/krow"
        assert by_name[name]["layer"] == "feed plane"
    assert by_name["h2d_ms_per_batch"]["workloads"] == [
        "resnet50_train_spark", "gpt2m_train_files"]
    assert by_name["h2d_ms_per_batch"]["layer"] == "infeed"
    for entry in by_name.values():
        assert entry["moves"] == "train_examples_per_s"
        assert entry["better"] == "lower"
        assert entry["source"] == "program_counter"


@pytest.mark.parametrize("which", ["BENCHMARK.json", "tiny"])
def test_entries_keep_the_manifests_contract(which):
    """What ``test_benchmark_manifest.py`` asks of a ``per_layer`` entry,
    asked of these against the manifest they are meant for."""
    if which == "tiny":
        on_disk, entries = _tiny.manifest(), _tiny_entries()
    else:
        on_disk, entries = _tiny.load(ROOT, "BENCHMARK.json"), \
            ENTRIES["per_layer"]
    cells = {w["name"] for w in on_disk["workloads"]}
    layers = {m["layer"] for m in on_disk["per_layer"]}
    moved = {m["name"]: set(m.get("workloads", cells))
             for m in on_disk["end_to_end"]}
    for entry in entries:
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert re.match(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$", entry["name"])
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", entry["unit"])
        assert entry["layer"] in layers        # a layer that is there
        assert entry["workloads"]
        assert set(entry["workloads"]) <= cells & moved[entry["moves"]]
        assert load_reader(on_disk, "layer_metrics", entry["name"])


def test_both_manifests_name_the_seven_or_neither_does():
    """The entries go into both files in one PR: ``run.py`` of the real
    manifest and the rehearsals of the tiny one read the same readers."""
    names = {e["name"] for e in ENTRIES["per_layer"]}
    real = {m["name"] for m in _tiny.load(ROOT, "BENCHMARK.json")["per_layer"]}
    tiny = {m["name"] for m in _tiny.manifest()["per_layer"]}
    assert names & real == names & tiny
    assert names & real in (set(), names)


@pytest.fixture(scope="module")
def own_manifest(tmp_path_factory):
    """The merged tiny manifest (its first fragment names the seven) with
    its two training cells under names of this file's own.  ``run.py`` keeps a cell's work under
    ``.perfbench_work/<cell name>`` and ``test_benchmark_run.py`` rehearses
    the same cells: under xdist the two files run at once and would empty
    each other's directory."""
    own = tmp_path_factory.mktemp("feed_cycle")
    manifest = _tiny.manifest()
    named = {m["name"]: m for m in manifest["per_layer"]}
    for entry in _tiny_entries():
        assert named[entry["name"]] == entry
    names = {"resnet_tiny_spark": "resnet_tiny_spark_fc",
             "gpt2_tiny_files": "gpt2_tiny_files_fc"}
    manifest["workloads"] = [dict(w, name=names[w["name"]])
                             for w in manifest["workloads"]
                             if w["name"] in names]
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [names[w] for w in metric["workloads"]
                                   if w in names]
    os.makedirs(own / "correctness")
    for old, new in names.items():
        shutil.copy(os.path.join(_tiny.TINY, "correctness", old + ".json"),
                    own / "correctness" / (new + ".json"))
    manifest["paths"] = [str(own)] + manifest["paths"]
    path = own / "manifest.json"
    path.write_text(json.dumps(manifest))
    return str(path)


def _rehearse(manifest, workload, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PERFBENCH_REHEARSAL_PLATFORM="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--manifest", manifest, "--workload", workload + "_fc",
         "--seed", str(2147483700 + trace), "--seconds", "2",
         "--trace", str(trace)],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_tiny_spark_rehearsal_prints_all_seven(own_manifest):
    result = _rehearse(own_manifest, "resnet_tiny_spark", 1)
    metrics = result["metrics"]
    assert set(FEED_METRICS + ["h2d_ms_per_batch"]) <= set(metrics), metrics
    for name in FEED_METRICS + ["h2d_ms_per_batch"]:
        value = metrics[name]["value"]
        assert math.isfinite(value) and value >= 0, (name, value)
    for name in FEED_METRICS:
        assert metrics[name]["unit"] == "ms/krow"
    # what it printed before is still there
    assert {"feed_rows_per_s", "infeed_host_ms_per_batch",
            "infeed_starved_pct", "dispatch_gap_ms.train",
            "compiles_in_window.train", "compile_cache_misses"} <= \
        set(metrics)
    assert result["correct"] is True


def test_tiny_files_rehearsal_prints_h2d_and_none_of_the_feed_ones(
        own_manifest):
    result = _rehearse(own_manifest, "gpt2_tiny_files", 1)
    metrics = result["metrics"]
    assert "h2d_ms_per_batch" in metrics
    assert metrics["h2d_ms_per_batch"]["value"] > 0
    assert not set(FEED_METRICS) & set(metrics)
    assert result["correct"] is True


def test_untraced_line_keeps_its_shape(own_manifest):
    result = _rehearse(own_manifest, "resnet_tiny_spark", 0)
    assert set(result["metrics"]) == {"train_examples_per_s", "setup_s"}
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
