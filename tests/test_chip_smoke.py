"""``chip_smoke.py`` rehearsed without the chip: its phase functions at tiny
sizes on the CPU (the same control flow, entry points and checks as on the
chip; nothing here is a device number), the parent's contract (last line,
exit code, refusal of a platform that is not ``tpu``, no jax in the parent),
and the fallbacks the smoke path turns into errors.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

TINY = {
    "feed": {"rows": 256, "batch": 16, "epochs": 1, "steps_per_call": 2,
             "log_steps": 2},
    "resnet": {"images": 32, "batch": 8, "image_size": 32, "store_px": 40,
               "steps_per_call": 2, "train_steps": 4, "epochs": 4,
               "blocks_per_stage": 1},
    "flash": {"layers": 1, "heads": 2, "head_dim": 16, "seq": 128,
              "vocab": 64, "batch": 2, "steps": 2},
    "serve": {"requests": 5, "max_batch": 4},
    "direct": {"requests": 5, "max_batch": 4},
    "mesh4": {"layers": 1, "heads": 2, "head_dim": 16, "seq": 128,
              "vocab": 64, "batch": 4, "steps": 2, "devices": 4},
    "pinned4": {"devices": 2},
}


@pytest.fixture
def one_cpu_device(monkeypatch):
    """Executors of the one-chip phases see one device, as on the chip."""
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")


def _phase(name, workdir):
    return chip_smoke.PHASES[name](7, str(workdir), TINY[name],
                                   platform="cpu")


def test_feed_phase(tmp_path, one_cpu_device):
    out = _phase("feed", tmp_path)
    assert out["ok"] and out["phase"] == "feed"
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    # the shell forked the user function before any backend existed
    assert out["shell_backend_at_fork"] is False
    # the rows went through the native shm ring, not the manager queue
    assert out["native_ring"] is True
    assert out["transport"]["wire_colv1"] > 0
    assert "wire_queue" not in out["transport"]
    assert out["steps"] == 16 and out["megastep"]["steps_per_call"] == 2
    assert out["loss_last"] < out["loss_first"]
    assert out["compile_secs"] > 0 and out["run_secs"] > 0
    assert out["dispatch_us_median"] > 0


@pytest.mark.slow
def test_resnet_export_serve_direct_phases(tmp_path, one_cpu_device):
    """The chain the smoke runs on the chip: the example's fed main_fun
    exports, one replica process serves the export, and in-process predict
    agrees with the replica's answers."""
    out = _phase("resnet", tmp_path)
    assert out["ok"] and out["steps"] == 4
    assert out["megastep"]["group_assembly"] == "device"
    assert np.isfinite(out["loss"])
    # the export carries BatchNorm's statistics and its own serving program
    assert out["export"]["variables"] == ["batch_stats", "params"]
    assert "tpu" in out["export"]["stablehlo"]["platforms"]

    served = _phase("serve", tmp_path)
    assert served["ok"] and served["requests"] == 5
    assert served["replica"]["from_stablehlo"] is True
    assert served["replica"]["platform"] == "cpu"
    assert served["replica_exit"] == 0  # SIGTERM-clean: the device is free

    direct = _phase("direct", tmp_path)
    assert direct["ok"] and direct["from_stablehlo"] is True
    assert direct["worst_deviation"] <= chip_smoke.SERVE_TOL


@pytest.mark.slow
def test_flash_phase(tmp_path, one_cpu_device):
    out = _phase("flash", tmp_path)
    assert out["ok"]
    assert set(out["kernel_parity"]) == {"out", "dq", "dk", "dv"}
    assert len(out["flash"]["losses"]) == len(out["full"]["losses"]) == 2
    # interpreted here, so the compiled-text check is the chip's
    assert "tpu_custom_calls" not in out


@pytest.mark.slow
def test_four_chip_phases_on_virtual_devices(tmp_path, monkeypatch):
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    out = _phase("mesh4", tmp_path)
    assert out["ok"]
    for layout in ("data4", "data2_tensor2"):
        assert any(out[layout]["collectives"].values())
        assert out[layout]["param_shard_devices"] == [0, 1, 2, 3]
    assert out["data2_tensor2"]["param_shard_shape"] != \
        out["data2_tensor2"]["param_shape"]
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    # two CPU processes do form one world; on a TPU host the pinned
    # executors stay one-chip worlds and the join is refused
    out = _phase("pinned4", tmp_path)
    assert out["ok"] and len(out["executors"]) == 2
    assert out["layout"] == "one world" and out["device"]["count"] == 2
    assert all(e["process_count"] == 2 for e in out["executors"])


# -- the parent's contract ---------------------------------------------------

TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _run_main(monkeypatch, capsys, argv, results, cache_dir=None):
    """``main`` with the native build and the phase children replaced by
    canned results; returns (exit code, the phases run, the last line)."""
    ran = []

    def fake_child(name, seed, workdir, timeout):
        ran.append(name)
        return dict(results.get(name, {"ok": True, "device": TPU}))

    monkeypatch.setattr(chip_smoke, "build_native", lambda: None)
    monkeypatch.setattr(chip_smoke, "_run_phase_child", fake_child)
    # setenv first: it records the variable's state for the undo, which a
    # delenv of an absent variable would not, and main() sets it
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir or "unset")
    if not cache_dir:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    code = chip_smoke.main(argv)
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[0] == "chip_smoke: compile cache at " + os.environ[
        "JAX_COMPILATION_CACHE_DIR"]
    return code, ran, lines[-1]


def test_last_line_is_the_contracts_object(monkeypatch, capsys):
    code, ran, last = _run_main(monkeypatch, capsys, ["--seed", "3"], {})
    assert code == 0 and tuple(ran) == chip_smoke.ONE_CHIP_PHASES
    assert json.loads(last) == {"ok": True, "device": TPU}
    # the cache path is the fixed one inside the checkout
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == os.path.join(
        ROOT, ".jax_cache")


def test_chips_4_runs_only_the_four_chip_phases(monkeypatch, capsys):
    four = dict(TPU, count=4)
    code, ran, last = _run_main(
        monkeypatch, capsys, ["--chips", "4"],
        {name: {"ok": True, "device": four}
         for name in chip_smoke.FOUR_CHIP_PHASES})
    assert code == 0 and tuple(ran) == chip_smoke.FOUR_CHIP_PHASES
    assert json.loads(last)["device"]["count"] == 4


def test_a_failed_phase_fails_the_run_but_later_phases_still_run(
        monkeypatch, capsys):
    code, ran, last = _run_main(
        monkeypatch, capsys, [],
        {"resnet": {"ok": False, "device": TPU, "error": "boom"}})
    assert code == 1 and tuple(ran) == chip_smoke.ONE_CHIP_PHASES
    assert json.loads(last) == {"ok": False, "device": TPU}


def test_a_phase_child_that_dies_counts_as_failed(tmp_path):
    """The exit code of the phase's child decides, whatever it left."""
    out = chip_smoke._run_phase_child("direct", 0, str(tmp_path), timeout=60)
    assert out["ok"] is False and "error" in out  # no export in tmp_path


def test_no_tpu_stops_at_the_first_phase(monkeypatch, capsys):
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    code, ran, last = _run_main(
        monkeypatch, capsys, [],
        {"feed": {"ok": False, "device": cpu, "error": "needs tpu"}})
    assert code == 1 and ran == ["feed"]
    assert json.loads(last) == {"ok": False, "device": cpu}
    # and phases that pass on something that is not a TPU do not make ok
    code, _, last = _run_main(
        monkeypatch, capsys, [],
        {name: {"ok": True, "device": cpu}
         for name in chip_smoke.ONE_CHIP_PHASES})
    assert code == 1 and json.loads(last)["ok"] is False


def test_externally_placed_cache_is_left_alone(monkeypatch, capsys):
    code, _, _ = _run_main(monkeypatch, capsys, [], {},
                           cache_dir="/some/dir")
    assert code == 0
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"


def test_missing_toolchain_is_an_error_not_a_fallback(monkeypatch, capsys,
                                                      tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(chip_smoke.shutil, "which", lambda name: None)
    with pytest.raises(chip_smoke.SmokeError, match=r"g\+\+ not found"):
        chip_smoke.build_native()
    assert chip_smoke.main([]) == 1  # and the run says ok: false
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1]) == {
        "ok": False, "device": None}


def test_whole_script_on_cpu_fails_without_importing_jax(tmp_path):
    """``python chip_smoke.py`` where JAX finds no accelerator: non-zero
    exit, ``"ok": false`` on the last line — and the parent process stayed
    off jax the whole way (asserted on its ``sys.modules``)."""
    code = (
        "import sys, chip_smoke\n"
        "rc = chip_smoke.main(['--seed', '1'])\n"
        "assert 'jax' not in sys.modules, 'the parent imported jax'\n"
        "print('PARENT_JAX_FREE')\n"
        "sys.exit(rc)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=240)
    lines = proc.stdout.strip().splitlines()
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert lines[-1] == "PARENT_JAX_FREE"
    last = json.loads(lines[-2])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert "this phase needs platform 'tpu'" in proc.stdout
    # the native libraries were built in this run, from native/*.cc
    assert "native libraries built from native/*.cc" in proc.stdout


def test_script_alone_in_a_directory_fails(tmp_path):
    """Without the program beside it the script exits non-zero and prints
    no passing result."""
    import shutil

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=60)
    assert proc.returncode == 1
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {
        "ok": False, "device": None}


# -- fallbacks that are errors on the smoke path ----------------------------

def test_platform_that_is_not_tpu_is_refused():
    report = {}
    with pytest.raises(chip_smoke.SmokeError, match="needs platform 'tpu'"):
        chip_smoke._open_device(report, "tpu")
    assert report["device"]["platform"] == "cpu"  # what it found, on record
    with pytest.raises(chip_smoke.SmokeError, match="needs 64 devices"):
        chip_smoke._open_device({}, "cpu", min_devices=64)


def test_stablehlo_platform_mismatch_is_an_error_on_the_smoke_path(tmp_path):
    """In production an artifact lowered for another platform degrades to
    the registry rebuild; the smoke refuses that and says which branch was
    taken."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import checkpoint, serving
    from tensorflowonspark_tpu.models import get_model

    model = get_model("linear", features=3)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4)))["params"]
    export_dir = str(tmp_path / "export")
    checkpoint.export_model(
        export_dir, params, "linear", model_config={"features": 3},
        input_signature={"x": [None, 4]}, model=model,
        serialize_platforms=("tpu",))
    server = serving.ModelServer(export_dir, batch_size=2)
    assert server.from_stablehlo is False
    assert "lowered for ['tpu']" in server.stablehlo_fallback
    server.predict_feed({"x": np.ones((2, 4), np.float32)}, 2)  # still serves
    with pytest.raises(chip_smoke.SmokeError, match="lowered for"):
        chip_smoke._require_stablehlo(server.from_stablehlo,
                                      server.stablehlo_fallback)


def test_sharded_loss_out_of_tolerance_is_an_error():
    chip_smoke._check_losses([2.0, 1.0], [2.0, 1.005], 1e-2, "a", "b")
    with pytest.raises(chip_smoke.SmokeError, match="rtol"):
        chip_smoke._check_losses([2.0, 1.0], [2.0, 1.2], 1e-2, "a", "b")
    with pytest.raises(chip_smoke.SmokeError, match="not finite"):
        chip_smoke._check_losses([2.0, float("nan")], [2.0, 1.0], 1e-2,
                                 "a", "b")
