#!/usr/bin/env python3
"""From a seed sweep (``tools/seed_sweep.py``) to a cell's limits file,
``benchmark/correctness/<cell>.json``: the per-seed readings, sound and
control, and the limits derived from them by one rule:

- a number whose control stands clear of the sound runs (``separating``, named
  on the command line after looking at the sweep) gets the geometric mean of
  the sound runs' largest and the control's smallest;
- every other number is there for a fault, not for precision, and gets three
  times the sound runs' largest;
- a number named under ``--unjudged`` gets no limit (it is printed by every
  run as read, not judged): one whose limit by the rule above would catch no
  fault that another number does not.

    python3 benchmark/tools/set_limits.py chiprun_out/<cell>.sweep.json \
        --separating loss_gap,grad_rel_diff --note "..."
"""

import argparse
import json
import math
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("sweep")
    parser.add_argument("--separating", default="")
    parser.add_argument("--unjudged", default="")
    parser.add_argument("--note", default="")
    parser.add_argument("--origin", default="my chip run, PR 23")
    args = parser.parse_args()
    with open(args.sweep) as f:
        sweep = json.load(f)
    separating = [s for s in args.separating.split(",") if s]
    unjudged = [s for s in args.unjudged.split(",") if s]
    runs = [r for r in sweep["runs"] if r.get("numbers")]
    names = sorted(runs[0]["numbers"])
    table, limits, rule = [], {}, {}
    for r in runs:
        table.append({"seed": r["seed"], "sound": r["numbers"],
                      "control": r.get("control_numbers"),
                      "problems": r.get("problems", [])})
    for name in names:
        sound = [r["numbers"][name] for r in runs]
        control = [r["control_numbers"][name] for r in runs
                   if r.get("control_numbers")]
        summary = {"sound_max": max(sound), "sound_min": min(sound),
                   "control_min": min(control) if control else None,
                   "control_max": max(control) if control else None}
        if name in separating:
            limit = math.sqrt(summary["sound_max"] * summary["control_min"])
            summary["rule"] = "geometric mean of sound_max and control_min"
            summary["ratio"] = summary["control_min"] / summary["sound_max"]
        else:
            limit = 3.0 * summary["sound_max"]
            summary["rule"] = "3 x sound_max (held against a fault)"
        if name in unjudged:
            summary["rule"] = "read, not judged"
        else:
            limits[name] = float("%.3g" % limit)
        rule[name] = summary
    out = {"cell": sweep["workload"], "origin": args.origin,
           "note": args.note, "limits": limits, "set_from": rule,
           "seeds": table}
    path = os.path.join(ROOT, "benchmark", "correctness",
                        sweep["workload"] + ".json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"limits": limits, "set_from": rule}, indent=1))


if __name__ == "__main__":
    main()
