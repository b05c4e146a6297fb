"""Plumbing tests for the shared tuning-ladder runner (scripts/ladder.py).

The tuning ladders (lm_tune / resnet_tune) stand on these guarantees, so
they get direct coverage with a trivial child (no jax, no device):
persist-after-every-variant, resume-skips-finished-variants, fresh child
scratch files, and cwd-independent output paths.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import ladder  # noqa: E402

CHILD_OK = ("import json,sys; json.dump({'variant': sys.argv[1], "
            "'ms_per_step': float(sys.argv[2])}, open(sys.argv[3],'w'))")


def _cmd(ms):
    def make(variant, child_out):
        return [sys.executable, "-c", CHILD_OK, variant, str(ms), child_out]
    return make


def test_ladder_runs_and_annotates_vs_baseline(tmp_path):
    out = str(tmp_path / "ladder.json")
    results = ladder.run_ladder(["baseline", "fast"], _cmd(10.0), out, 30)
    rows = {r["variant"]: r for r in results["rows"]}
    assert rows["baseline"]["vs_baseline"] == 1.0
    # persisted artifact matches the return value
    with open(out) as f:
        assert json.load(f)["rows"] == results["rows"]
    # child scratch files are cleaned up
    assert not [p for p in os.listdir(tmp_path) if p != "ladder.json"]


def test_ladder_resumes_prior_rows(tmp_path):
    out = str(tmp_path / "ladder.json")
    # first window: only one variant completed, one errored
    with open(out, "w") as f:
        json.dump({"rows": [
            {"variant": "baseline", "ms_per_step": 7.0},
            {"variant": "slow", "error": "timeout after 1s"}]}, f)
    results = ladder.run_ladder(["baseline", "slow"], _cmd(14.0), out, 30)
    rows = {r["variant"]: r for r in results["rows"]}
    # baseline reused from the prior run (NOT re-measured at 14.0)...
    assert rows["baseline"]["ms_per_step"] == 7.0
    # ...the errored variant re-ran and succeeded this time
    assert rows["slow"]["ms_per_step"] == 14.0
    assert "error" not in rows["slow"]
    assert rows["slow"]["vs_baseline"] == 0.5


def test_ladder_ignores_stale_child_files(tmp_path):
    out = str(tmp_path / "ladder.json")
    # a stale scratch file from a crashed run must not be read as fresh
    with open(out + ".baseline", "w") as f:
        json.dump({"variant": "baseline", "ms_per_step": 999.0}, f)
    fail = [sys.executable, "-c", "import sys; sys.exit(3)"]
    results = ladder.run_ladder(
        ["baseline"], lambda v, c: fail, out, 30)
    (row,) = results["rows"]
    assert row["error"] == "rc=3"
    assert "ms_per_step" not in row


def test_ladder_out_path_is_cwd_independent(tmp_path):
    # the parent records results where --out said, even when children run
    # with a different cwd
    out = str(tmp_path / "sub" / "ladder.json")
    os.makedirs(os.path.dirname(out))
    results = ladder.run_ladder(["baseline"], _cmd(3.0), out, 30,
                                cwd=str(tmp_path))
    assert os.path.exists(out)
    assert results["rows"][0]["ms_per_step"] == 3.0


def test_ladder_failed_run_keeps_error_row_and_timeout(tmp_path):
    out = str(tmp_path / "ladder.json")
    hang = [sys.executable, "-c", "import time; time.sleep(60)"]
    results = ladder.run_ladder(["baseline"], lambda v, c: hang, out, 1)
    (row,) = results["rows"]
    assert row["error"] == "timeout after 1s"
    with open(out) as f:
        assert json.load(f)["rows"][0]["error"] == "timeout after 1s"


def test_tune_scripts_share_the_runner_schema():
    """Both tune CLIs emit the runner's `rows` schema — the watcher's
    ladder_done() counts error-free rows against the script's VARIANTS."""
    import lm_tune
    import resnet_tune

    assert len(lm_tune.VARIANTS) >= 6
    assert len(resnet_tune.VARIANTS) >= 6
