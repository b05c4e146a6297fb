#!/usr/bin/env python3
"""Cut the head of a traced run's profile into a fixture for the trace
reduction's tests (``benchmark/fixtures/*.json.gz``):

    python3 benchmark/run.py --workload <cell> --seed 1 --seconds 5 \\
        --trace 1 --details chiprun_out/x.json
    python3 benchmark/tools/record_fixture.py chiprun_out/x.json.trace \\
        benchmark/fixtures/<name>.json.gz [--seconds 0.2]
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402


def dump_head(planes, path, seconds):
    """Write the head of a trace as a fixture (gzipped JSON of ``load``'s
    plain data): device events and the host spans (the benchmark's and the
    program's) that start in the first ``seconds`` of the window, names cut
    short, the window span clipped to that head, and of each device plane's
    ``origins`` those of the events kept."""
    import gzip
    import json

    window = [(s, e) for n, s, e, _ in trace_reduce.host_spans(planes)
              if n == trace_reduce.SPAN_PREFIX + "window"]
    lo = window[0][0] if window else min(
        ev[1] for p in planes for ln in p["lines"] for ev in ln["events"])
    hi = lo + int(seconds * 1e9)
    out = []
    for plane in planes:
        device = plane["name"].startswith("/device:")
        where, kept, lines = plane.get("origins") or {}, {}, []
        for line in plane["lines"]:
            events = []
            for name, start, dur in line["events"]:
                if name == trace_reduce.SPAN_PREFIX + "window":
                    events.append([name, lo, hi - lo])
                elif lo <= start < hi and (
                        device or name.startswith(
                            trace_reduce.SPAN_PREFIXES)):
                    short = trace_reduce.short_name(name, 64)
                    events.append([short, start, min(dur, hi - start)])
                    if name in where:
                        kept[short] = where[name]
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            out.append({"name": plane["name"], "lines": lines,
                        "origins": kept})
    with gzip.open(path, "wt") as f:
        json.dump(out, f)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("trace_dir")
    parser.add_argument("out")
    parser.add_argument("--seconds", type=float, default=0.2)
    args = parser.parse_args()
    planes = trace_reduce.load(trace_reduce.find_xplane(args.trace_dir))
    dump_head(planes, args.out, args.seconds)


if __name__ == "__main__":
    main()
