#!/usr/bin/env python3
"""Run one cell over many seeds, each with the lower-precision control, and
collect what was compared: the readings every limit in
``benchmark/correctness/<cell>.json`` is set from.

    python3 benchmark/tools/seed_sweep.py --workload <cell> --seeds 12 \
        --seconds 5 --out chiprun_out/<cell>.sweep.json [--first-seed N]

Each seed is one run of ``benchmark/run.py`` (a process of its own, so a chip
has one owner at a time) with ``--control 1``.
"""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--first-seed", type=int, default=2147480000)
    parser.add_argument("--seconds", type=float, default=5)
    parser.add_argument("--control", type=int, default=1)
    parser.add_argument("--out", required=True)
    args = parser.parse_args()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    runs = []
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        details = args.out + ".seed%d.json" % seed
        cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
               "--workload", args.workload, "--seed", str(seed),
               "--seconds", str(args.seconds), "--trace", "0",
               "--control", str(args.control), "--details", details]
        t0 = time.time()
        done = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
        run = {"seed": seed, "exit": done.returncode,
               "wall_s": round(time.time() - t0, 1)}
        lines = done.stdout.strip().splitlines()
        try:
            run["result"] = json.loads(lines[-1])
        except (IndexError, ValueError):
            run["stderr_tail"] = done.stderr[-3000:]
        if os.path.exists(details):
            with open(details) as f:
                report = json.load(f)
            for key in ("numbers", "control_numbers", "losses", "problems",
                        "reference_secs", "memory_peak_bytes", "sampled"):
                if key in report:
                    run[key] = report[key]
            run["window"] = {k: v for k, v in report.get("window", {}).items()
                             if not k.startswith("counters")}
            os.remove(details)
        print("sweep: " + json.dumps(run, default=float), flush=True)
        for line in lines[:-1]:
            if line.startswith("perfbench:"):
                print("  " + line, flush=True)
        runs.append(run)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "runs": runs}, f, indent=1,
                      default=float)
    good, bad = {}, {}
    for run in runs:
        for name, v in run.get("numbers", {}).items():
            good.setdefault(name, []).append(v)
        for name, v in run.get("control_numbers", {}).items():
            bad.setdefault(name, []).append(v)
    for name in sorted(good):
        line = "sweep: {}: sound max {:.6g} (min {:.6g})".format(
            name, max(good[name]), min(good[name]))
        if name in bad:
            line += "; control min {:.6g} (max {:.6g}); ratio {:.2f}".format(
                min(bad[name]), max(bad[name]),
                min(bad[name]) / max(good[name]))
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
