#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json``: its configuration file, its
traffic mix (``<path>/traffic/<traffic>.json``, which names the driver), and
every metric's reader (``<path>/end_to_end/<name>.py`` for ``--trace 0``,
``<path>/layer_metrics/<name>.py`` for ``--trace 1``), each searched for under
the manifest's ``paths`` in order.  This process never imports jax: the
driver starts the one process that holds the chip.  The last line of standard
output is the result, one JSON object; without a TPU, with a compile inside
the window, or with a ``device_kind`` that ``peaks.json`` does not know, the
exit code is not 0 and no result is printed.
"""

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def find(manifest, *parts):
    """The first ``<path>/<parts...>`` that exists, over the manifest's
    ``paths``."""
    for base in manifest["paths"]:
        path = os.path.join(ROOT, base, *parts)
        if os.path.exists(path):
            return path
    return None


def load_reader(manifest, kind, name):
    path = find(manifest, kind, name + ".py")
    if path is None:
        raise SystemExit("no reader {}/{}.py under {}".format(
            kind, name, manifest["paths"]))
    if os.path.dirname(path) not in sys.path:   # a reader's helpers
        sys.path.insert(0, os.path.dirname(path))
    spec = importlib.util.spec_from_file_location(
        "perfbench_{}_{}".format(kind, name.replace(".", "_")), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def metrics_of(manifest, cell, kind, report):
    """{name: {"value", "unit"}} of every metric of ``kind`` that lists the
    cell (or lists none) and whose reader finds something to read."""
    out = {}
    dirname = "end_to_end" if kind == "end_to_end" else "layer_metrics"
    reported = {m["name"] for m in manifest["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])}
    for metric in manifest[kind]:
        if cell["name"] not in metric.get("workloads", [cell["name"]]):
            continue
        if kind == "per_layer" and metric["moves"] not in reported:
            continue
        value = load_reader(manifest, dirname, metric["name"])(report)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # the builder's own: also run the lower-precision control of the
    # reference (never in the driver's runs), another manifest (the tests'
    # tiny one), an offered rate other than the mix's (the sweep)
    parser.add_argument("--control", type=int, default=0)
    parser.add_argument("--manifest",
                        default=os.path.join(ROOT, "BENCHMARK.json"))
    parser.add_argument("--rate", type=float, default=None)
    parser.add_argument("--details", default=None,
                        help="also write the whole report here (JSON), and "
                        "leave a traced run's profile beside it")
    args = parser.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "tensorflowonspark_tpu")):
        print("perfbench: no tensorflowonspark_tpu package beside benchmark/:"
              " nothing to measure", file=sys.stderr)
        return 2
    with open(args.manifest) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print("perfbench: no workload {!r}".format(args.workload),
              file=sys.stderr)
        return 2
    cell = cells[args.workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(find(manifest, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    limits_path = find(manifest, "correctness", cell["name"] + ".json")
    limits = {}
    if limits_path:
        with open(limits_path) as f:
            limits = json.load(f).get("limits", {})

    # one compile cache at a fixed place, shared by every process of the run
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["PYTHONPATH"] = os.pathsep.join(
        p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)
    sys.path.insert(0, ROOT)
    workdir = os.path.join(ROOT, ".perfbench_work", cell["name"])
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)

    run_args = argparse.Namespace(
        cell=cell["name"], config=config, traffic=traffic, seed=args.seed,
        seconds=args.seconds, trace=args.trace, control=args.control,
        chips=cell["chips"], t_start=T_START, rate=args.rate,
        deadline_secs=1100)
    driver = importlib.import_module("benchmark.drivers." + traffic["driver"])
    try:
        report = driver.run(run_args, workdir)
    finally:
        if not args.details:
            shutil.rmtree(workdir, ignore_errors=True)
    if args.details:
        os.makedirs(os.path.dirname(os.path.abspath(args.details)),
                    exist_ok=True)
        with open(args.details, "w") as f:
            json.dump(report, f, default=float)
        if os.path.isdir(os.path.join(workdir, "trace")):
            # the profile itself, for tools/record_fixture.py
            shutil.rmtree(args.details + ".trace", ignore_errors=True)
            shutil.move(os.path.join(workdir, "trace"),
                        args.details + ".trace")
        shutil.rmtree(workdir, ignore_errors=True)
    if not report.get("ok"):
        print("perfbench: the run gave no result:\n{}".format(
            report.get("error")), file=sys.stderr)
        return 3 if report.get("bench_error") else 1
    built = report["window"]["compiles"]
    if built["backend_compiles"] or built["cache_hits"] \
            or built["cache_misses"]:
        print("perfbench: a program was compiled or loaded inside the "
              "measured window: {}".format(built), file=sys.stderr)
        return 3

    window = report["window"]
    window["delta"] = {
        group: {k: v - window["counters0"].get(group, {}).get(k, 0)
                for k, v in after.items() if isinstance(v, (int, float))}
        for group, after in window.get("counters1", {}).items()}

    from benchmark import correctness

    said = []
    correct, rows = correctness.verdict(report, limits, out=said.append)
    print("\n".join(said), flush=True)

    device = {"platform": report["device"]["platform"],
              "kind": report["device"]["kind"],
              "count": report["device"]["count"],
              "memory_peak_bytes": report.get("memory_peak_bytes")}
    result = {"correct": correct, "attempted": report["attempted"],
              "failed": report["failed"]}
    if args.trace:
        trace = report.get("trace") or {}
        device["busy_s"] = trace.get("busy_s")
        device["window_s"] = trace.get("window_s")
        result["metrics"] = metrics_of(manifest, cell, "per_layer", report)
        result["breakdown"] = {"device_ops": trace.get("device_ops", []),
                               "idle_gaps": trace.get("idle_gaps", [])}
    else:
        result["metrics"] = metrics_of(manifest, cell, "end_to_end", report)
    result["device"] = device
    # each number compared beside its limit: last in the line, and again as
    # the last lines of standard error (what the driver's record keeps of a
    # run that is not correct)
    result["compared"] = dict(
        {name: {"value": value if value is not None and value == value
                and abs(value) != float("inf") else None, "limit": limit}
         for name, value, limit, _ in rows},
        problems={"value": len(report.get("problems", [])), "limit": 0})
    print(json.dumps(result), flush=True)
    print("\n".join(said), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
