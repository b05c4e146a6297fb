"""The set-up's six per-layer metrics (PR 39): each reader on a hand-made
report (the value by hand; ``None``, never an exception, on anything missing,
zero where it may not be, or not a number), their ``per_layer`` entries in
both manifests, and the tiny FILES and SPARK rehearsals printing them.

They read ``report["window"]["counters0"]["trainer"]``: the cumulative
values of ``Trainer.counters_snapshot()`` one hook call after the window
opens, where the bring-up's account (``telemetry.bringup``) and the compile
plane's tallies (``compilecache.stats.tallies()``) stand as the set-up left
them."""

import copy
import json
import math
import os
import shutil
import subprocess
import sys

import pytest

import _tiny
from _tiny import ROOT

sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from run import load_reader  # noqa: E402  (benchmark/run.py: never imports jax)

PHASES = ("driver", "spawn", "node", "rendezvous", "launch", "user",
          "trainer_init", "first_batch", "first_dispatch")

TRAINER = {
    "dispatch_count": 8,
    "bringup_driver_us": 40000, "bringup_spawn_us": 2100000,
    "bringup_node_us": 60000, "bringup_rendezvous_us": 12500,
    "bringup_launch_us": 1787500, "bringup_user_us": 15000000,
    "bringup_trainer_init_us": 2250000, "bringup_first_batch_us": 0,
    "bringup_first_dispatch_us": 6750000, "bringup_wall_us": 28000000,
    "compile_trace_us": 5000000, "compile_lower_us": 4000000,
    "compile_backend_us": 250000, "compile_cache_retrieval_us": 3250000,
    "compile_programs": 25,
}
REPORT = {"window": {"seconds": 10.0,
                     "counters0": {"trainer": TRAINER, "infeed": {}},
                     "counters1": {"trainer": dict(TRAINER,
                                                   dispatch_count=70)}}}

BY_HAND = {
    "setup_cluster_s": 4.0,          # 0.04 + 2.1 + 0.06 + 0.0125 + 1.7875
    "setup_rendezvous_s": 0.0125,
    "setup_trainer_init_s": 2.25,
    "setup_first_batch_s": 0.0,      # the feed was ready before the loop asked
    "setup_compile_s": 12.5,         # 5 + 4 + 0.25 + 3.25
    "setup_programs": 25.0,
}

# metric -> the counters it adds up
READS = {
    "setup_cluster_s": ["bringup_driver_us", "bringup_spawn_us",
                        "bringup_node_us", "bringup_rendezvous_us",
                        "bringup_launch_us"],
    "setup_rendezvous_s": ["bringup_rendezvous_us"],
    "setup_trainer_init_s": ["bringup_trainer_init_us"],
    "setup_first_batch_s": ["bringup_first_batch_us"],
    "setup_compile_s": ["compile_trace_us", "compile_lower_us",
                        "compile_backend_us", "compile_cache_retrieval_us"],
    "setup_programs": ["compile_programs"],
}
LAYERS = {"setup_cluster_s": "driver and rendezvous",
          "setup_rendezvous_s": "driver and rendezvous",
          "setup_trainer_init_s": "step loop",
          "setup_first_batch_s": "infeed",
          "setup_compile_s": "compile plane",
          "setup_programs": "compile plane"}


@pytest.fixture(scope="module")
def manifest():
    return _tiny.load(ROOT, "BENCHMARK.json")


def _reader(manifest, name):
    return load_reader(manifest, "layer_metrics", name)


def test_the_made_up_account_adds_up():
    assert sum(TRAINER["bringup_%s_us" % p] for p in PHASES) == \
        TRAINER["bringup_wall_us"]


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_gives_the_value_worked_by_hand(manifest, name):
    assert _reader(manifest, name)(REPORT) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_reads_the_opening_and_not_the_window(manifest, name):
    """The set-up is over when the window opens: what the counters do
    inside the window moves nothing."""
    report = copy.deepcopy(REPORT)
    report["window"]["counters1"]["trainer"] = {
        k: 3 * v for k, v in TRAINER.items()}
    report["window"]["delta"] = {"trainer": {k: 2 * v
                                             for k, v in TRAINER.items()}}
    assert _reader(manifest, name)(report) == pytest.approx(BY_HAND[name])


def _broken(name, how):
    keys = READS[name]
    report = copy.deepcopy(REPORT)
    at_open = report["window"]["counters0"]
    if how == "no group":
        del at_open["trainer"]
    elif how == "group is None":
        at_open["trainer"] = None
    elif how == "no key":
        del at_open["trainer"][keys[-1]]
    elif how == "no account":      # the parent: a trainer without any of it
        at_open["trainer"] = {"dispatch_count": 8}
    elif how == "a string":
        at_open["trainer"][keys[0]] = "12"
    elif how == "None":
        at_open["trainer"][keys[0]] = None
    elif how == "nan":
        at_open["trainer"][keys[-1]] = float("nan")
    elif how == "a bool":
        at_open["trainer"][keys[0]] = True
    elif how == "negative":
        at_open["trainer"][keys[0]] = -1
    elif how == "no counters0":
        del report["window"]["counters0"]
    elif how == "counters0 a list":
        report["window"]["counters0"] = [TRAINER]
    elif how == "no window":
        report = {}
    return report


@pytest.mark.parametrize("how", [
    "no group", "group is None", "no key", "no account", "a string", "None",
    "nan", "a bool", "negative", "no counters0", "counters0 a list",
    "no window"])
@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_finds_nothing_and_does_not_raise(manifest, name, how):
    assert _reader(manifest, name)(_broken(name, how)) is None


@pytest.mark.parametrize("name", sorted(set(BY_HAND) - {"setup_first_batch_s"}))
def test_a_zero_where_there_may_be_none_reads_as_nothing(manifest, name):
    """A cluster that came up in no time, a trainer built in none, a process
    that made no program: the counter is not there yet, not 0."""
    report = copy.deepcopy(REPORT)
    for key in READS[name]:
        report["window"]["counters0"]["trainer"][key] = 0
    assert _reader(manifest, name)(report) is None


def test_first_batch_may_be_zero_but_only_beside_a_whole_account(manifest):
    read = _reader(manifest, "setup_first_batch_s")
    assert read(REPORT) == 0.0
    report = copy.deepcopy(REPORT)
    report["window"]["counters0"]["trainer"]["bringup_first_batch_us"] = 1500
    assert read(report) == pytest.approx(0.0015)
    del report["window"]["counters0"]["trainer"]["bringup_wall_us"]
    assert read(report) is None


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_both_manifests_name_the_metric_over_their_training_cells(manifest,
                                                                  name):
    """Appended to ``BENCHMARK.json`` over all its cells by name, and to the
    rehearsal manifest by the fragment ``90_bringup.json`` over its training
    cells; ``program_counter``, lower is better, and it moves ``setup_s``."""
    tiny = _tiny.manifest()
    for merged, cells in ((manifest, {w["name"]
                                      for w in manifest["workloads"]}),
                          (tiny, set(tiny["end_to_end"][0]["workloads"]))):
        entry, = [m for m in merged["per_layer"] if m["name"] == name]
        assert entry["source"] == "program_counter"
        assert entry["moves"] == "setup_s" and entry["better"] == "lower"
        assert entry["layer"] == LAYERS[name]
        assert entry["unit"] == ("count" if name == "setup_programs" else "s")
        assert set(entry["workloads"]) == cells
    fragment = _tiny.load(_tiny.TINY, "manifest.d", "90_bringup.json")
    assert set(fragment) == {"what", "per_layer"}
    assert name in {m["name"] for m in fragment["per_layer"]}
    # the six are the last entries of the real manifest, in the issue's order
    assert [m["name"] for m in manifest["per_layer"][-6:]] == [
        "setup_cluster_s", "setup_rendezvous_s", "setup_trainer_init_s",
        "setup_first_batch_s", "setup_compile_s", "setup_programs"]


@pytest.fixture(scope="module")
def own_manifest(tmp_path_factory):
    """The merged tiny manifest with its two rehearsed cells under names of
    this file's own: ``run.py`` keeps a cell's work under
    ``.perfbench_work/<cell name>`` and other test files rehearse the same
    cells at the same time under xdist."""
    own = tmp_path_factory.mktemp("bringup")
    manifest = _tiny.manifest()
    names = {"resnet_tiny_spark": "resnet_tiny_spark_bu",
             "gpt2_tiny_files": "gpt2_tiny_files_bu"}
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


def _rehearse(manifest, workload, trace, details=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PERFBENCH_REHEARSAL_PLATFORM="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--manifest", manifest, "--workload", workload + "_bu",
         "--seed", str(2147483900 + trace), "--seconds", "2",
         "--trace", str(trace)] + (["--details", details] if details else []),
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["gpt2_tiny_files", "resnet_tiny_spark"])
def test_tiny_rehearsal_prints_all_six_and_the_account_adds_up(
        own_manifest, workload, tmp_path):
    """FILES (the start task is the chip-holding process) and SPARK (its
    forked child is): a traced line holds the six as numbers, the nine
    phases at the window's opening sum to ``bringup_wall_us`` exactly, and
    ``setup_s`` holds them all."""
    details = str(tmp_path / "report.json")
    result = _rehearse(own_manifest, workload, 1, details)
    metrics = result["metrics"]
    assert set(BY_HAND) <= set(metrics), metrics
    for name in BY_HAND:
        value = metrics[name]["value"]
        assert math.isfinite(value) and value >= 0, (name, value)
        assert metrics[name]["unit"] == (
            "count" if name == "setup_programs" else "s")
    assert metrics["setup_cluster_s"]["value"] > \
        metrics["setup_rendezvous_s"]["value"] > 0
    assert metrics["setup_programs"]["value"] >= 1
    assert metrics["setup_compile_s"]["value"] > 0
    # what it printed before is still there
    assert {"infeed_host_ms_per_batch", "infeed_starved_pct",
            "dispatch_gap_ms.train", "compiles_in_window.train",
            "compile_cache_misses"} <= set(metrics)
    assert result["correct"] is True
    with open(details) as f:
        report = json.load(f)
    at_open = report["window"]["counters0"]["trainer"]
    phases = [at_open["bringup_%s_us" % p] for p in PHASES]
    assert all(isinstance(v, int) and v >= 0 for v in phases), phases
    assert sum(phases) == at_open["bringup_wall_us"]
    assert at_open["bringup_spawn_us"] > 0
    # the account lies inside the set-up, and the compile time inside both
    assert at_open["bringup_wall_us"] / 1e6 < report["window"]["setup_s"]
    assert metrics["setup_compile_s"]["value"] < \
        at_open["bringup_wall_us"] / 1e6
    # nothing was made inside the window, and no step program twice
    closing = report["window"]["counters1"]["trainer"]
    assert closing["compile_programs"] == at_open["compile_programs"]
    assert "train_recompiles_total" not in closing


def test_untraced_line_keeps_its_shape(own_manifest):
    result = _rehearse(own_manifest, "gpt2_tiny_files", 0)
    assert set(result["metrics"]) == {"train_examples_per_s", "setup_s"}
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
