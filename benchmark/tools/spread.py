#!/usr/bin/env python3
"""Two sets of runs of one cell at its full length, the same seeds in both,
and each end-to-end metric's spread: the distance between the first and third
quartile (``statistics.quantiles(values, n=4)``) as a share of the median,
per set; the wider of the two is what a bound is set from (about five times
the widest over the cells, never under 1%).

    python3 benchmark/tools/spread.py --workload <cell> --seconds 20 \
        --runs 6 --out chiprun_out/<cell>.spread.json
"""

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--first-seed", type=int, default=2147400011)
    parser.add_argument("--out", required=True)
    args = parser.parse_args()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    sets = []
    for s in range(2):
        rows = []
        for k in range(args.runs):
            seed = args.first_seed + 104729 * k
            done = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                 "--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(args.seconds), "--trace", "0"],
                cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True)
            lines = done.stdout.strip().splitlines()
            row = {"set": s, "seed": seed, "exit": done.returncode}
            try:
                result = json.loads(lines[-1])
                row["correct"] = result["correct"]
                row["failed"] = result["failed"]
                row["memory_peak_bytes"] = result["device"][
                    "memory_peak_bytes"]
                row["metrics"] = {k2: v["value"]
                                  for k2, v in result["metrics"].items()}
            except (IndexError, ValueError):
                row["stderr_tail"] = done.stderr[-2000:]
            row["notes"] = [ln for ln in lines[:-1]
                            if "NOT ok" in ln or "window" in ln
                            or "requests" in ln]
            # the trainer's own log (a line every ``log_steps`` steps, with
            # the time): the rate inside the window, for whoever looks
            row["step_log"] = [" ".join(ln.split()[1:2] + ln.split()[5:8])
                               for ln in done.stderr.splitlines()
                               if " examples/sec " in ln]
            print("run: " + json.dumps(row), flush=True)
            rows.append(row)
        sets.append(rows)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "seconds": args.seconds,
                       "sets": sets}, f, indent=1)
    names = sorted(sets[0][0].get("metrics", {}))
    summary = {}
    for name in names:
        per_set = []
        for rows in sets:
            values = [r["metrics"][name] for r in rows if "metrics" in r]
            per_set.append({"median": statistics.median(values),
                            "spread": spread(values), "values": values})
        summary[name] = {
            "sets": per_set,
            "widest_spread": max(p["spread"] for p in per_set),
            "second_median_vs_first": per_set[1]["median"]
            / per_set[0]["median"] - 1.0}
        print("spread: {} medians {:.6g} / {:.6g} ({:+.2%}), spreads "
              "{:.3%} / {:.3%}".format(
                  name, per_set[0]["median"], per_set[1]["median"],
                  summary[name]["second_median_vs_first"],
                  per_set[0]["spread"], per_set[1]["spread"]), flush=True)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "seconds": args.seconds,
                   "sets": sets, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
