"""``benchmark/run.py`` end to end at tiny sizes on the CPU (the rehearsal),
the refusal to run without a chip, and a run whose timed path is broken."""

import json
import os
import subprocess
import sys
import types

import pytest

import _tiny
from _tiny import ROOT

@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    """The merged rehearsal manifest, written where ``run.py`` can read it."""
    return _tiny.manifest_path(tmp_path_factory.mktemp("manifest"))


def _run(manifest, workload, trace, rehearse=True, seed=5, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PERFBENCH_REHEARSAL_PLATFORM", None)
    if rehearse:
        env["PERFBENCH_REHEARSAL_PLATFORM"] = "cpu"
    env.pop("XLA_FLAGS", None)
    if devices > 1:
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%d"
                            % devices)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--manifest", manifest, "--workload", workload, "--seed", str(seed),
         "--seconds", "2", "--trace", str(trace)],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=600)


@pytest.mark.parametrize("workload,trace,metrics", [
    ("gpt2_tiny_files", 0, {"train_examples_per_s", "setup_s"}),
    ("gpt2_tiny_files", 1, {"infeed_host_ms_per_batch", "infeed_starved_pct",
                            "dispatch_gap_ms.train",
                            "compiles_in_window.train",
                            "compile_cache_misses"}),
    ("resnet_tiny_spark", 0, {"train_examples_per_s", "setup_s"}),
    ("resnet_tiny_spark", 1, {"feed_rows_per_s", "infeed_starved_pct"}),
    ("resnet_tiny_serve", 0, {"serve_p95_ms", "serve_goodput_rps",
                              "setup_s"}),
    ("resnet_tiny_serve", 1, {"serve_queue_ms_p95", "loadgen_late_ms_p95"}),
    # the traffic mix's ``mesh`` layout, on four forced host devices
    ("gpt2_tiny_mesh4", 0, {"train_examples_per_s", "setup_s"}),
])
def test_rehearsal_prints_the_result_line(manifest, workload, trace, metrics):
    done = _run(manifest, workload, trace, seed=2147483659 + trace,
                devices=4 if workload.endswith("mesh4") else 1)
    assert done.returncode == 0, done.stderr[-3000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert metrics <= set(result["metrics"]), result["metrics"]
    for value in result["metrics"].values():
        assert set(value) == {"value", "unit"}
    assert result["attempted"] > 0 and result["failed"] == 0
    if trace:
        assert {"busy_s", "window_s"} <= set(result["device"])
        assert "breakdown" in result
    if workload.endswith("mesh4"):
        assert result["device"]["count"] == 4
    if workload != "resnet_tiny_serve":
        # the tiny cells have limits: the whole comparison ran and held
        assert result["correct"] is True, done.stdout[-2000:]
        assert "compared grad_rel_diff" in done.stdout
        # every number compared beside its limit: last in the line, and the
        # last lines of standard error
        assert list(result)[-1] == "compared"
        held = result["compared"]
        assert held["problems"] == {"value": 0, "limit": 0}
        assert 0 <= held["grad_rel_diff"]["value"] <= \
            held["grad_rel_diff"]["limit"]
        assert "compared grad_rel_diff" in "\n".join(
            done.stderr.strip().splitlines()[-8:])


def test_without_a_chip_there_is_no_result(manifest):
    """The real path: no rehearsal switch, JAX finds only the CPU."""
    done = _run(manifest, "gpt2_tiny_files", 0, rehearse=False)
    assert done.returncode not in (0, None)
    assert "needs platform 'tpu'" in done.stderr
    assert not any(line.startswith("{") for line in done.stdout.splitlines())


def test_unknown_device_kind_is_an_error():
    from benchmark import harness

    with pytest.raises(harness.BenchError):
        harness.load_peaks("TPU v9 imaginary")
    with pytest.raises(harness.BenchError):
        harness.load_peaks("_source")
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def _drive(config, seed, directory):
    """The training driver's whole run in this process: everything but the
    look for a chip and the cluster around the executor.  The rows travel by
    the FILES transport, which carries either adapter's."""
    import importlib

    from benchmark import correctness
    from benchmark.drivers import train_feed

    cfg = _tiny.config(config)
    traffic = _tiny.load(_tiny.TINY, "traffic", "token_files_tiny.json")
    cell = _tiny.training_cells()[config]
    limits = _tiny.load(_tiny.TINY, "correctness", cell + ".json")["limits"]
    adapter = importlib.import_module("benchmark.adapters." + cfg["adapter"])
    args = types.SimpleNamespace(
        config=cfg, traffic=traffic, seed=seed, chips=1, seconds=1.0,
        trace=0, control=0, t_start=0.0,
        shards=train_feed._write_shards(adapter, cfg, seed, traffic,
                                        str(directory)))
    ctx = types.SimpleNamespace(initialize_distributed=lambda: None)
    report = {}
    train_feed._train(args, ctx, report)
    lines = []
    correct, rows = correctness.verdict(report, limits, out=lines.append)
    return correct, report, {name for name, _, _, ok in rows if not ok}, lines


def _unchanged_state(monkeypatch):
    """``optax.apply_updates`` hands the parameters back as they came."""
    import optax

    monkeypatch.setattr(optax, "apply_updates", lambda params, updates: params)


def _half_batch(monkeypatch):
    """The program's loss leaves the second half of every batch out."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import resnet, transformer

    def halved(make):
        def loss_fn(*args, **kwargs):
            loss = make(*args, **kwargs)

            def faulty(*call):
                mask = call[-1]
                keep = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
                return loss(*call[:-1], mask * keep)

            return faulty
        return loss_fn

    monkeypatch.setattr(resnet, "loss_fn", halved(resnet.loss_fn))
    monkeypatch.setattr(transformer, "loss_fn", halved(transformer.loss_fn))


def _frozen_statistics(monkeypatch):
    """The program's loss hands BatchNorm's statistics back as they came."""
    from tensorflowonspark_tpu.models import resnet

    make = resnet.loss_fn

    def loss_fn(*args, **kwargs):
        loss = make(*args, **kwargs)

        def faulty(params, stats, batch, mask):
            value, aux = loss(params, stats, batch, mask)
            return value, dict(aux, extra_state=stats)

        return faulty

    monkeypatch.setattr(resnet, "loss_fn", loss_fn)


@pytest.mark.parametrize("config,fault,caught_by", [
    ("gpt2_tiny", None, set()),
    ("resnet_tiny", None, set()),
    ("gpt2_tiny", _unchanged_state, {"delta_norm_gap"}),
    ("resnet_tiny", _unchanged_state, {"delta_norm_gap"}),
    ("gpt2_tiny", _half_batch, {"grad_rel_diff"}),
    ("resnet_tiny", _half_batch, {"grad_rel_diff"}),
    ("resnet_tiny", _frozen_statistics, {"extra_rel_diff"}),
])
def test_a_broken_timed_path_is_not_correct(config, fault, caught_by,
                                            tmp_path, monkeypatch):
    """Drive a whole run with the timed path broken underneath, and see
    ``correct`` come out false by the numbers that are there for that fault
    (and true, with nothing failing, where nothing is broken)."""
    monkeypatch.setenv("PERFBENCH_REHEARSAL_PLATFORM", "cpu")
    if fault:
        fault(monkeypatch)
    correct, report, failed, lines = _drive(config, 21, tmp_path)
    print("\n".join(lines))
    assert correct == (fault is None), lines
    assert caught_by <= failed, (failed, lines)
    if fault is _unchanged_state:
        assert report["numbers"]["delta_norm_gap"] == pytest.approx(1.0)
    if fault is _frozen_statistics:
        assert report["numbers"]["extra_rel_diff"] == pytest.approx(1.0)
