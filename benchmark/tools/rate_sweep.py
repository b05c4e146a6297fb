#!/usr/bin/env python3
"""Offer a serving cell a ladder of fixed rates, one run of ``run.py`` each,
and print latency and goodput at every rate: the table the cell's fixed rate
(0.8 x the knee) and its latency limit (4 x the median at the lowest rate,
rounded up to 10 ms) are read from, once.

    python3 benchmark/tools/rate_sweep.py --workload resnet50_serve_open \
        --rates 10,20,40,80,120,160 --seconds 10 --out chiprun_out/rates.json
"""

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=10)
    parser.add_argument("--seed", type=int, default=2147481111)
    parser.add_argument("--out", required=True)
    args = parser.parse_args()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    table = []
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        details = args.out + ".rate%g.json" % rate
        done = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", args.workload, "--seed", str(args.seed + k),
             "--seconds", str(args.seconds), "--trace", "0",
             "--rate", str(rate), "--details", details],
            cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        row = {"rate_rps": rate, "exit": done.returncode}
        if os.path.exists(details):
            with open(details) as f:
                report = json.load(f)
            w = report.get("window", {})
            row.update(
                requests=w.get("requests"), failed=w.get("failed"),
                good=w.get("good"), latency_ms=w.get("latency_ms"),
                late_ms_p95=w.get("late_ms_p95"), setup_s=w.get("setup_s"),
                answered_rps=(w.get("requests", 0) - w.get("failed", 0))
                / w["seconds"] if w.get("seconds") else None,
                numbers=report.get("numbers"),
                memory_peak_bytes=report.get("memory_peak_bytes"))
            d0 = w.get("counters0", {}).get("replica", {})
            d1 = w.get("counters1", {}).get("replica", {})
            row["replica"] = {k2: d1[k2] - d0.get(k2, 0) for k2 in (
                "serving_requests", "serving_rows", "serving_batches",
                "serving_shed") if k2 in d1}
            os.remove(details)
        else:
            row["stderr_tail"] = done.stderr[-2000:]
        print("rate: " + json.dumps(row, default=float), flush=True)
        table.append(row)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rates": table}, f,
                      indent=1, default=float)
    return 0


if __name__ == "__main__":
    sys.exit(main())
