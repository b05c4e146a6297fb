"""Contract tests for the headline bench's leg machinery (bench.py).

The bench is the round's graded artifact, but until now no test drove any
of its legs — a leg that only ever ran on the (rarely reachable) TPU could
break silently.  These tests run the cheapest real leg end-to-end on the
CPU backend with the same env knobs the bench itself documents, plus the
pure-plumbing pieces (the device probe, the exit code).  The conv legs (resnet) are
excluded: XLA conv compiles take minutes on 1-core CI hosts (the bench's
own RESNET_BLOCKS smoke knob exists for exactly that reason).
"""

import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LM_SMOKE_ENV = {
    "TFOS_BENCH_LM_BATCH": "2", "TFOS_BENCH_LM_SEQ": "64",
    "TFOS_BENCH_LM_LAYERS": "2", "TFOS_BENCH_LM_HEADS": "2",
    "TFOS_BENCH_LM_VOCAB": "256", "TFOS_BENCH_LM_STEPS": "4",
    # the leg runs single-device like the real bench; without this the
    # conftest's 8-virtual-device XLA_FLAGS leak into the subprocess and
    # the tiny smoke batch isn't divisible by the mesh
    "XLA_FLAGS": "",
}


def _run_leg(tmp_path, leg, extra_env):
    out = str(tmp_path / (leg + ".json"))
    env = dict(os.environ)
    env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"),
         "--leg", leg, "--out", out],
        cwd=ROOT, env=env, timeout=300, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(out) as f:
        return json.load(f)


def test_transformer_leg_contract(tmp_path):
    """The transformer leg (K>1 scan path) emits the stats fields the
    bench aggregator consumes, and names the device it ran on."""
    stats = _run_leg(tmp_path, "transformer",
                     dict(LM_SMOKE_ENV, TFOS_BENCH_LM_SPC="2"))
    assert stats["global_steps"] == 4
    assert stats["avg_step_seconds"] > 0
    assert "mfu" not in stats  # a CPU run reports no utilization
    assert stats["platform"] == "cpu"
    assert stats["n_devices"] >= 1 and stats["device_kind"]


def test_transformer_leg_k1_path(tmp_path):
    """steps_per_call=1 exercises the plain-step branch of
    _run_synthetic_leg (shared with the resnet leg)."""
    stats = _run_leg(tmp_path, "transformer",
                     dict(LM_SMOKE_ENV, TFOS_BENCH_LM_SPC="1",
                          TFOS_BENCH_LM_STEPS="3"))
    assert stats["global_steps"] == 3
    assert stats["avg_step_seconds"] > 0


def test_remat_mfu_uses_analytic_model_flops():
    """A remat LM trainer's MFU numerator must be the analytic MODEL
    FLOPs, not XLA cost analysis of the executed program (which would
    count the rematerialized forward as if it were model progress)."""
    sys.path.insert(0, ROOT)
    try:
        import bench
    finally:
        sys.path.remove(ROOT)
    import jax

    # batch divisible by the conftest's 8-virtual-device data axis
    b, s, layers, heads, vocab = 16, 32, 2, 2, 128
    trainer, batch, mask, cfg = bench.build_lm_trainer(
        batch_size=b, seq=s, layers=layers, heads=heads, vocab=vocab,
        remat=True, log_steps=10 ** 9)
    assert cfg["remat"] is True
    assert cfg["mfu_numerator"] == "analytic_model_flops"
    d = heads * 64
    fwd = b * s * (24 * d * d * layers + 2 * d * vocab)
    fwd += 4 * s * s * 64 * b * heads * layers
    want = 3 * fwd // max(len(jax.devices()), 1)
    assert trainer.step_flops_override == want
    trainer.step(batch)  # history builds on first step
    assert trainer.history.step_flops == want


def test_lm_tune_ladder_smoke(tmp_path):
    """The lm_tune ladder (scripts/lm_tune.py) runs a variant end-to-end
    on CPU and persists the aggregate JSON after each variant."""
    out = str(tmp_path / "lm_tune.json")
    env = dict(os.environ)
    env.update(LM_SMOKE_ENV, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "lm_tune.py"),
         "--variants", "baseline", "--k", "2", "--repeats", "1",
         "--out", out],
        cwd=ROOT, env=env, timeout=300, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(out) as f:
        results = json.load(f)
    (row,) = results["rows"]
    assert row["variant"] == "baseline"
    assert row["ms_per_step"] > 0
    assert row["config"]["seq"] == 64  # env knobs reached the child
    assert "mfu_pct" not in row  # a CPU run reports no utilization


def _import_bench():
    sys.path.insert(0, ROOT)
    try:
        import bench
    finally:
        sys.path.remove(ROOT)
    return bench


def test_probe_hard_timeout_kills_process_group():
    """The probe's timeout is HARD: a child that wedges (here: sleeps past
    the deadline) is SIGKILLed with its whole process group, and the
    caller sees TimeoutExpired promptly instead of hanging on the pipe."""
    bench = _import_bench()
    t0 = time.monotonic()
    with pytest.raises(subprocess.TimeoutExpired):
        bench._probe_subprocess("import time; time.sleep(60)", timeout=1.0)
    assert time.monotonic() - t0 < 10.0


def test_probe_reports_what_answered(monkeypatch):
    """The probe returns what the child found — platform, kind, device
    count — or the reason it found nothing; it does not retry."""
    bench = _import_bench()
    calls = []

    def answered(code, timeout):
        calls.append(timeout)
        return (0, '{"kind": "TPU v5 lite", "platform": "tpu", '
                   '"device_count": 1}\n', "")

    monkeypatch.setattr(bench, "_probe_subprocess", answered)
    found, err = bench.probe_device(timeout=5)
    assert err is None
    assert found == {"kind": "TPU v5 lite", "platform": "tpu",
                     "device_count": 1}

    def hung(code, timeout):
        calls.append(timeout)
        raise subprocess.TimeoutExpired(cmd="probe", timeout=timeout)

    monkeypatch.setattr(bench, "_probe_subprocess", hung)
    found, err = bench.probe_device(timeout=1)
    assert found is None and "timed out" in err
    monkeypatch.setattr(bench, "_probe_subprocess",
                        lambda code, timeout: (1, "", "no backend"))
    found, err = bench.probe_device(timeout=1)
    assert found is None and "no backend" in err
    assert len(calls) == 2  # one attempt each: no retry, no back-off


@pytest.mark.parametrize("probe, why", [
    (({"platform": "cpu", "kind": "cpu", "device_count": 1}, None),
     "no accelerator"),
    ((None, "device probe timed out after 120s"), "timed out"),
])
def test_no_chip_fails_the_run(monkeypatch, capsys, probe, why):
    """A run that finds no chip runs no device leg, replays nothing and
    exits non-zero; the host legs still report."""
    bench = _import_bench()
    monkeypatch.setattr(bench, "probe_device", lambda: probe)
    ran = []

    def fake_leg(leg, retries=1):
        ran.append(leg)
        return {"items_per_sec": 10.0, "backend": "cpu"}, None

    monkeypatch.setattr(bench, "run_leg_isolated", fake_leg)
    assert bench.main() == 1
    assert not {"mnist", "resnet", "transformer"} & set(ran)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["device_legs_failed"] == ["mnist", "resnet", "transformer"]
    assert out["value"] is None and why in out["resnet50_error"]
    assert "replayed_legs" not in out and "value_source" not in out
    assert out["feed_plane_images_per_sec"] == 10.0


def test_device_leg_that_produces_nothing_fails_the_run(monkeypatch, capsys):
    bench = _import_bench()
    monkeypatch.setattr(bench, "probe_device", lambda: (
        {"platform": "tpu", "kind": "TPU v5 lite", "device_count": 1}, None))

    def fake_leg(leg, retries=1):
        if leg == "resnet":
            return None, "leg resnet rc=1 (attempt 2)"
        return {"items_per_sec": 10.0, "avg_exp_per_second": 100.0,
                "avg_step_seconds": 0.1, "mfu": 0.1, "platform": "tpu",
                "device_kind": "TPU v5 lite", "n_devices": 1}, None

    monkeypatch.setattr(bench, "run_leg_isolated", fake_leg)
    assert bench.main() == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["device_legs_failed"] == ["resnet"]
    assert out["leg_platforms"]["mnist"] == "tpu"
    assert out["device"]["platform"] == "tpu"
    monkeypatch.setattr(
        bench, "run_leg_isolated",
        lambda leg, retries=1: fake_leg("mnist"))
    assert bench.main() == 0
