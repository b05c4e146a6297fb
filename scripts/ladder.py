"""Shared per-variant subprocess ladder runner (lm_tune / resnet_tune).

One variant per fresh interpreter (XLA flags and libtpu knobs only apply
at client creation; server-side compile state and HBM reset too), one
output schema (``{"utc", ..., "rows": [...]}``), and three guarantees:

- **persist-after-every-variant**: a killed ladder keeps the finished
  rows;
- **resume**: a re-run loads the prior artifact and skips variants that
  already have an error-free row, so a ladder completes across runs none
  of which is long enough for the whole set;
- **fresh child files**: the per-variant scratch JSON is deleted before
  the child spawns and after the parent reads it — a stale file from an
  earlier run can never masquerade as this run's measurement.

Paths resolve against the parent's cwd ONCE (``abspath``) so passing
``cwd=`` for the children (they import ``bench`` from the repo root)
can't redirect where results land.
"""

import json
import os
import subprocess
import sys
import time


def _persist(out_path, results):
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)


def run_ladder(variants, make_cmd, out_path, timeout, meta=None,
               env_for=None, cwd=None, label="ladder"):
    """Run ``variants`` through child subprocesses; returns the results
    dict (also persisted to ``out_path`` after every variant).

    ``make_cmd(variant, child_out) -> argv`` builds the child command;
    ``env_for(variant) -> dict | None`` optionally overrides its env.
    """
    out_path = os.path.abspath(out_path)
    prior = {}
    try:
        with open(out_path) as f:
            for row in json.load(f).get("rows", []):
                if "error" not in row and row.get("variant"):
                    prior[row["variant"]] = row
    except (OSError, ValueError):
        pass

    results = dict(meta or {})
    results["utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    results["rows"] = []
    for variant in variants:
        if variant in prior:
            results["rows"].append(prior[variant])
            _persist(out_path, results)
            print("[%s] %s: reusing row from prior run" % (label, variant),
                  flush=True)
            continue
        child_out = out_path + "." + variant
        try:
            os.remove(child_out)
        except OSError:
            pass
        t0 = time.time()
        try:
            proc = subprocess.run(
                make_cmd(variant, child_out), cwd=cwd,
                env=env_for(variant) if env_for else None, timeout=timeout)
            if proc.returncode == 0 and os.path.exists(child_out):
                with open(child_out) as f:
                    row = json.load(f)
            else:
                row = {"variant": variant, "error": "rc=%d" % proc.returncode}
        except subprocess.TimeoutExpired:
            row = {"variant": variant, "error": "timeout after %ds" % timeout}
        try:
            os.remove(child_out)
        except OSError:
            pass
        row["elapsed_s"] = round(time.time() - t0, 1)
        results["rows"].append(row)
        _persist(out_path, results)
        print("[%s] %s -> %s" % (label, variant, json.dumps(row)),
              flush=True)

    # speedups relative to the ladder's own baseline row, when present
    base = next((r.get("ms_per_step") for r in results["rows"]
                 if r.get("variant") == "baseline"), None)
    if base:
        for r in results["rows"]:
            if r.get("ms_per_step"):
                r["vs_baseline"] = round(base / r["ms_per_step"], 3)
        _persist(out_path, results)
    print("[%s] wrote %s" % (label, out_path), flush=True)
    return results
