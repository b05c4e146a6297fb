"""The rule of ``benchmark/README.md`` ("Adding things"), held: a new family,
a new cell and a new per-layer metric are new files and new entries, and no
file that exists is edited.

The tree's ``benchmark/`` and ``tests/benchmark/`` are copied to a temporary
directory, a third family is laid over the copy (``third_family/`` beside
this file: a two-layer MLP on token rows with its reference, adapter, count
with ``kernels``, configuration, mix, limits, two per-layer metrics with
their readers, one fragment, and the entries for ``BENCHMARK.json``), and
there, with nothing that was copied changed, the family's tiny cell runs
through ``run.py`` on the CPU, the manifest's tests pass, and the tests that
hold every family's reference to the program take the new one in."""

import hashlib
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

import _tiny
from _tiny import ROOT

OVERLAY = os.path.join(ROOT, "tests", "benchmark", "third_family")
ENTRIES = "BENCHMARK.entries.json"


def _files(top):
    for directory, dirs, names in os.walk(top):
        dirs[:] = [d for d in dirs if d not in ("__pycache__",
                                                "third_family")]
        for name in names:
            if not name.endswith(".pyc"):
                yield os.path.relpath(os.path.join(directory, name), top)


def _digest(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def lay_out(root):
    """The copy with the third family over it; {relative path: digest} of
    every file that was copied."""
    copied = {}
    for part in ("benchmark", os.path.join("tests", "benchmark")):
        for rel in _files(os.path.join(ROOT, part)):
            target = os.path.join(root, part, rel)
            os.makedirs(os.path.dirname(target), exist_ok=True)
            shutil.copy(os.path.join(ROOT, part, rel), target)
            copied[os.path.join(part, rel)] = _digest(target)
    # the program itself is not the benchmark's: the copy points at it
    for name in ("tensorflowonspark_tpu", "examples"):
        os.symlink(os.path.join(ROOT, name), os.path.join(root, name))
    for rel in _files(OVERLAY):
        if rel == ENTRIES:
            continue
        target = os.path.join(root, rel)
        assert not os.path.exists(target), "the overlay would edit " + rel
        os.makedirs(os.path.dirname(target), exist_ok=True)
        shutil.copy(os.path.join(OVERLAY, rel), target)
    # BENCHMARK.json apart: entries are appended to it
    manifest = _tiny.merge_fragment(_tiny.load(ROOT, "BENCHMARK.json"),
                                    _tiny.load(OVERLAY, ENTRIES), ENTRIES)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return copied


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("third_family"))
    return root, lay_out(root)


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PERFBENCH_REHEARSAL_PLATFORM="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("PYTHONPATH", None)
    return env


def _rehearse(root, trace):
    manifest = _tiny.manifest_path(
        root, os.path.join(root, "tests", "benchmark", "tiny"))
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--manifest", manifest, "--workload", "mlp_tiny_files",
         "--seed", str(2147483740 + trace), "--seconds", "2",
         "--trace", str(trace)],
        cwd=root, env=_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stdout


def test_the_new_familys_tiny_cell_runs_with_its_own_metric_in_the_line(copy):
    root, _ = copy
    result, out = _rehearse(root, 1)
    assert result["correct"] is True, out[-2000:]
    assert "compared grad_rel_diff" in out
    metrics = result["metrics"]
    assert metrics["mlp_hidden_model_tflops"]["value"] > 0
    assert metrics["mlp_hidden_model_tflops"]["unit"] == "TFLOP/s"
    # the readers every training cell shares read the new family's run too
    assert {"infeed_host_ms_per_batch", "dispatch_gap_ms.train",
            "compiles_in_window.train", "compile_cache_misses",
            "h2d_ms_per_batch"} <= set(metrics)
    # no device trace on the CPU: a share of a roofline is left out, never 0
    assert "mlp_hidden_roofline_pct" not in metrics


def test_the_new_familys_untraced_line(copy):
    root, _ = copy
    result, out = _rehearse(root, 0)
    assert result["correct"] is True, out[-2000:]
    assert set(result["metrics"]) == {"train_examples_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0


def _pytest_in(root, *args):
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly"] + list(args),
        cwd=root, env=_env(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=900)


def test_the_manifests_tests_pass_on_the_copy(copy):
    """``BENCHMARK.json`` with the family's entries appended, the merged
    rehearsal manifest with its fragment: the contract, the files each name
    resolves to, and every family with a tiny cell."""
    root, _ = copy
    done = _pytest_in(root, os.path.join("tests", "benchmark",
                                         "test_benchmark_manifest.py"))
    assert done.returncode == 0, done.stdout[-3000:]
    families = {(c["reference"], c["adapter"]) for c in (
        _tiny.load(root, e["file"])
        for e in _tiny.load(root, "BENCHMARK.json")["configs"])}
    assert ("mlp", "mlp") in families


def test_every_familys_reference_tests_take_the_new_one_in(copy):
    """``test_benchmark_references.py`` holds each family's tiny cell to its
    limits, its fp8 control to failing them, and the float32 program to the
    reference, by the merged manifest: the new family's seven without an
    edit."""
    root, _ = copy
    done = _pytest_in(root, "-k", "mlp_tiny", os.path.join(
        "tests", "benchmark", "test_benchmark_references.py"))
    assert done.returncode == 0, done.stdout[-3000:]
    assert "7 passed" in done.stdout, done.stdout[-600:]


def _module(root, *parts):
    """A file of the copy as a module (its helpers beside it importable)."""
    path = os.path.join(root, *parts)
    sys.path.insert(0, os.path.dirname(path))
    try:
        spec = importlib.util.spec_from_file_location(
            "third_" + "_".join(parts).replace(".", "_"), path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(os.path.dirname(path))
    return module


def _reader(root, name):
    return _module(root, "benchmark", "layer_metrics", name + ".py").read


def _report(root):
    """A chip's traced report by hand: 4 profiled steps of the tiny
    configuration, 2 microseconds of device time under the hidden layer."""
    cfg = _tiny.load(root, "tests", "benchmark", "tiny", "configs",
                     "mlp_tiny.json")
    counts = _module(root, "benchmark", "counts", "mlp.py")
    return {"window": {"chips": 1},
            "model": {"kernels": counts.kernels(cfg)},
            "device": {"peaks": {"bf16_flops_per_s": 197e12,
                                 "hbm_bytes_per_s": 819e9}},
            "trace": {"steps": 4, "by_scope": {"mlp/hidden": 2e-6,
                                               "mlp": 5e-6}}}


def test_a_scopes_roofline_share_by_hand(copy):
    root, _ = copy
    read = _reader(root, "mlp_hidden_roofline_pct")
    rows = 8 * 31
    flops = 6 * rows * 32 * 64
    moved = 6 * rows * (32 + 64) + 4 * 32 * 64
    # this small, the layer is bound by its bytes, not its FLOPs
    assert moved / 819e9 > flops / 197e12
    assert read(_report(root)) == pytest.approx(
        100.0 * (moved / 819e9) * 4 / 2e-6)


@pytest.mark.parametrize("missing", [
    ("trace",), ("trace", "by_scope"), ("trace", "by_scope", "mlp/hidden"),
    ("trace", "steps"), ("model", "kernels"),
    ("model", "kernels", "mlp/hidden"), ("device", "peaks")])
def test_a_roofline_share_with_nothing_to_read_is_none_not_zero(copy,
                                                                missing):
    root, _ = copy
    report = _report(root)
    inner = report
    for key in missing[:-1]:
        inner = inner[key]
    del inner[missing[-1]]
    assert _reader(root, "mlp_hidden_roofline_pct")(report) is None


def test_nothing_that_was_copied_was_edited(copy):
    """After all of the above: every copied file is byte for byte what it
    was (``git diff --stat`` of the copied files is empty), and what the
    family added is only new files."""
    root, copied = copy
    for rel, digest in copied.items():
        assert _digest(os.path.join(root, rel)) == digest, rel
    added = {rel for rel in _files(OVERLAY) if rel != ENTRIES}
    assert added and not added & set(copied)
    assert {os.path.dirname(rel) for rel in added} >= {
        os.path.join("benchmark", d) for d in (
            "references", "adapters", "counts", "configs", "layer_metrics",
            "correctness")}
