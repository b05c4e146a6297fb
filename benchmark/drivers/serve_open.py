"""Driver of the serving cells: one replica (``inference_cli --serve``, the
program's own entry point, called in a child that holds the chip) answers an
open loop of requests that this process, which never imports jax, sends on a
fixed schedule through ``gateway.ServingClient``.

The child makes the export from the seeded weights, then *is* the replica:
its main thread runs ``inference_cli.main([... "--serve" ...])``.  A side
thread of the benchmark's in the same process takes commands on standard
input (open and close the window: compile counts, peak memory; in a traced
run, profile some seconds of load after it) and, when told to finish, sends the process the SIGTERM that the CLI
drains on; the reference then runs in that process, the program's state gone.

Latency is taken from the instant a request was *due*, so a stall shows in
every request behind it; how late the generator itself sent is reported.
"""

import json
import os
import queue
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REPLICA_ID = "perfbench-r0"
CTL = "perfbench-ctl "


# ---------------------------------------------------------------------------
# The child: export, then the replica itself
# ---------------------------------------------------------------------------

def _say(**message):
    print(CTL + json.dumps(message, default=float), flush=True)


def child_main(spec_path):
    sys.path.insert(0, ROOT)
    from benchmark import harness

    with open(spec_path) as f:
        spec = json.load(f)
    with harness.reporting(spec["result_path"]) as report:
        try:
            _child(spec, report)
        finally:
            _say(event="exit")


def _child(spec, report):
    import importlib

    import numpy as np

    from benchmark import correctness, generate, harness

    cfg, traffic, seed = spec["config"], spec["traffic"], spec["seed"]
    watch = harness.CompileWatch()
    report["device"] = harness.open_device(spec["chips"])
    adapter = importlib.import_module("benchmark.adapters." + cfg["adapter"])
    reference = importlib.import_module(
        "benchmark.references." + cfg["reference"])
    adapter.export(cfg, seed, spec["export_dir"])
    _say(event="exported")

    state = {}
    trace = (harness.WindowTrace(spec["trace_dir"]) if spec["trace"]
             else None)

    def control():
        for line in sys.stdin:
            cmd = json.loads(line)
            if cmd["cmd"] == "window_start":
                state["compiles0"] = watch.mark()
                _say(event="window_open")
            elif cmd["cmd"] == "profile_start":
                trace.start()
                trace.open()
                _say(event="profile_open")
            elif cmd["cmd"] == "window_end":
                if trace:
                    state["trace"] = trace.stop()
                state["compiles1"] = watch.mark()
                state["memory_peak_bytes"] = harness.memory_peak_bytes()
                _say(event="window_closed")
            elif cmd["cmd"] == "finish":
                state["sample_path"] = cmd["sample_path"]
                os.kill(os.getpid(), signal.SIGTERM)
                return

    threading.Thread(target=control, name="perfbench-control",
                     daemon=True).start()

    from tensorflowonspark_tpu import inference_cli

    inference_cli.main([
        "--export_dir", spec["export_dir"], "--serve", "--port", "0",
        "--max-batch", str(max(cfg["serve_buckets"])),
        "--max-wait-ms", str(traffic["max_wait_ms"]),
        "--roster", spec["roster"], "--replica-id", REPLICA_ID,
        "--heartbeat", str(traffic["heartbeat_secs"])])
    # the replica has drained and gone; what it held on the device is free
    if "sample_path" not in state:
        raise harness.BenchError("the replica ended before it was told to")
    report["trace"] = state.get("trace")
    report["memory_peak_bytes"] = state["memory_peak_bytes"]
    report["compiles"] = {k: state["compiles1"][k] - state["compiles0"][k]
                          for k in state["compiles0"]}
    report["process_compiles"] = state["compiles1"]

    with np.load(state["sample_path"]) as sample:
        ids = [int(k) for k in sample.files]
        replies = [sample[str(i)] for i in ids]
    sizes = [len(r) for r in replies]
    pool = generate.image_pool(seed, traffic, cfg["image_size"])
    images = np.concatenate([
        generate.request_images(seed, i, n, pool) for i, n in zip(ids, sizes)])
    t0 = time.perf_counter()
    ref = reference.predict(cfg, seed, images)
    report["reference_secs"] = time.perf_counter() - t0
    split = np.cumsum(sizes)[:-1]
    report["numbers"] = correctness.serving_numbers(
        replies, np.split(ref, split))
    report["sampled"] = {"requests": len(ids), "images": int(sum(sizes))}
    if spec["control"]:
        ctl = reference.predict(cfg, seed, images, precision="fp8")
        report["control_numbers"] = correctness.serving_numbers(
            np.split(ctl, split), np.split(ref, split))


# ---------------------------------------------------------------------------
# The parent: roster, load generator, bookkeeping (never imports jax)
# ---------------------------------------------------------------------------

class _Lines(object):
    """The child's standard output, line by line, with its control messages
    parsed; everything is echoed to this process's standard error."""

    def __init__(self, process):
        self.q = queue.Queue()

        def pump():
            for line in process.stdout:
                sys.stderr.write(line)
                self.q.put(line.rstrip("\n"))
            self.q.put(None)

        threading.Thread(target=pump, daemon=True).start()

    def wait_for(self, accept, timeout):
        """The first line for which ``accept(line)`` is not None."""
        deadline = time.time() + timeout
        while True:
            try:
                line = self.q.get(timeout=max(0.1, deadline - time.time()))
            except queue.Empty:
                raise RuntimeError("the replica's process said nothing in "
                                   "{} s".format(timeout))
            if line is None:
                raise RuntimeError("the replica's process ended early")
            found = accept(line)
            if found is not None:
                return found

    def event(self, name, timeout):
        def accept(line):
            if line.startswith(CTL):
                msg = json.loads(line[len(CTL):])
                if msg.get("event") == "exit" and name != "exit":
                    raise RuntimeError("the replica's process gave up")
                if msg.get("event") == name:
                    return msg
            return None

        return self.wait_for(accept, timeout)


def _send_load(addr, schedule, payload, keep, threads, timeout):
    """Send ``schedule`` ([(due, images)]) open loop from ``threads`` callers,
    each with a channel of its own; returns one record a request:
    (due, late, latency or None, ok) and the kept replies."""
    from tensorflowonspark_tpu import gateway

    import numpy as np

    records = [None] * len(schedule)
    replies = {}
    cursor = [0]
    lock = threading.Lock()
    t0 = time.perf_counter() + 0.05

    def caller():
        client = gateway.ServingClient(replicas=[addr], timeout=timeout)
        try:
            while True:
                with lock:
                    i = cursor[0]
                    cursor[0] += 1
                if i >= len(schedule):
                    return
                due, n = schedule[i]
                x = payload(i, n)
                wait = t0 + due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                sent = time.perf_counter()
                try:
                    out = client.predict({"image": x}, n)["output"]
                    done = time.perf_counter()
                    ok = out.shape[0] == n and bool(np.isfinite(out).all())
                    if i in keep:
                        replies[i] = np.asarray(out)
                    records[i] = (due, sent - t0 - due, done - t0 - due, ok)
                except Exception as e:  # shed, timed out, refused: a failure
                    records[i] = (due, sent - t0 - due, None, False)
                    print("perfbench: request {} failed: {!r}".format(i, e),
                          file=sys.stderr)
        finally:
            client.close()

    pool = [threading.Thread(target=caller, daemon=True)
            for _ in range(threads)]
    for t in pool:
        t.start()
    for t in pool:
        t.join(timeout=schedule[-1][0] + timeout + 30)
    return records, replies


def _percentile(values, q):
    """Nearest-rank percentile of a list with Nones counted as +inf."""
    ordered = sorted(float("inf") if v is None else v for v in values)
    rank = max(0, min(len(ordered) - 1,
                      int(-(-q * len(ordered) // 1)) - 1))
    return ordered[rank]


def run(args, workdir):
    import numpy as np

    from benchmark import generate
    from tensorflowonspark_tpu import reservation

    cfg, traffic, seed = args.config, args.traffic, args.seed
    rate = args.rate or traffic["rate_rps"]
    seconds = traffic["trace_seconds"] if args.trace else args.seconds
    spec = {"config": cfg, "traffic": traffic, "seed": seed,
            "chips": args.chips, "trace": args.trace,
            "control": args.control,
            "result_path": os.path.join(workdir, "report.json"),
            "export_dir": os.path.join(workdir, "export"),
            "trace_dir": os.path.join(workdir, "trace")}
    # the roster is here for the replica's counters only: its liveness
    # monitor must never declare the replica dead over a late beat
    resv = reservation.Server(1, heartbeat_interval=traffic["heartbeat_secs"],
                              heartbeat_misses=10 ** 6)
    host, port = resv.start()
    spec["roster"] = "{}:{}".format(host, port)
    spec_path = os.path.join(workdir, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", spec_path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, cwd=workdir,
        start_new_session=True)

    def tell(**cmd):
        child.stdin.write(json.dumps(cmd) + "\n")
        child.stdin.flush()

    def replica_counters():
        return dict(resv.metrics_snapshot()["nodes"].get(REPLICA_ID, {}))

    loadgen, error = {}, None
    try:
        lines = _Lines(child)
        pool = generate.image_pool(seed, traffic, cfg["image_size"])
        addr = lines.wait_for(
            lambda ln: ln.split(" ready on ")[1].split()[0]
            if " ready on " in ln else None, args.deadline_secs)

        def payload(i, n):
            return generate.request_images(seed, i, n, pool)

        # warm-up: every size the mix sends, through the callers' own path
        sizes = sorted(int(k) for k in traffic["size_weights"])
        warm = [(0.02 * j, n) for j, n in enumerate(
            sizes * traffic["warm_rounds"])]
        records, _ = _send_load(
            addr, warm, lambda i, n: payload(10 ** 6 + i, n), set(), 4,
            traffic["timeout_secs"])
        if not all(r and r[3] for r in records):
            raise RuntimeError("a warm-up request failed")
        schedule = generate.arrivals(seed, traffic, seconds, rate)
        keep = generate.sample_requests(seed, schedule, traffic)
        time.sleep(2 * traffic["heartbeat_secs"])
        tell(cmd="window_start")
        lines.event("window_open", 60)
        before = replica_counters()
        wall0 = time.time()
        records, replies = _send_load(
            addr, schedule, payload, keep, traffic["client_threads"],
            traffic["timeout_secs"])
        time.sleep(2 * traffic["heartbeat_secs"])   # the window's last beat
        after = replica_counters()
        if args.trace:
            # the profiler slows the gateway for as long as it runs: the
            # window above was timed and counted with it off; now profile
            # some seconds more of the same load for what the trace alone
            # can say (the replica's device-busy time, its operations)
            tell(cmd="profile_start")
            lines.event("profile_open", 120)
            more = generate.arrivals(seed + 1, traffic,
                                     traffic["trace_profile_seconds"], rate)
            _send_load(addr, more, lambda i, n: payload(2 * 10 ** 6 + i, n),
                       set(), traffic["client_threads"],
                       traffic["timeout_secs"])
        tell(cmd="window_end")
        lines.event("window_closed", 120)
        sample_path = os.path.join(workdir, "sample.npz")
        np.savez(sample_path, **{str(i): replies[i] for i in sorted(replies)})
        tell(cmd="finish", sample_path=sample_path)
        lines.event("exit", 600)
        code = child.wait(timeout=60)
        limit = traffic["latency_limit_ms"] / 1e3
        latency = [r[2] if r and r[3] else None for r in records]
        good = sum(1 for v in latency if v is not None and v <= limit)
        loadgen = {
            "seconds": seconds, "rate_rps": rate, "requests": len(schedule),
            "images": int(sum(n for _, n in schedule)),
            "failed": sum(1 for v in latency if v is None),
            "good": good, "setup_s": wall0 - args.t_start,
            "latency_ms": {"p50": 1e3 * _percentile(latency, 0.50),
                           "p95": 1e3 * _percentile(latency, 0.95),
                           "p99": 1e3 * _percentile(latency, 0.99)},
            "late_ms_p95": 1e3 * _percentile(
                [r[1] if r else None for r in records], 0.95),
            # for whoever looks into a far-off run: every request's reply
            # time minus due time, in order of due time (ms; -1 = failed)
            "latency_ms_series": [-1 if v is None else round(1e3 * v, 1)
                                  for v in latency],
            "sample_missing": sorted(set(keep) - set(replies)),
            "counters0": {"replica": before}, "counters1": {"replica": after},
            "child_exit": code}
        print("perfbench: {} requests ({} images) offered at {:.1f}/s over "
              "{:.1f} s; {} within {} ms, {} failed; latency ms p50 {:.1f} "
              "p95 {:.1f} p99 {:.1f}; generator late p95 {:.2f} ms".format(
                  len(schedule), loadgen["images"], rate, seconds, good,
                  traffic["latency_limit_ms"], loadgen["failed"],
                  loadgen["latency_ms"]["p50"], loadgen["latency_ms"]["p95"],
                  loadgen["latency_ms"]["p99"], loadgen["late_ms_p95"]),
              flush=True)
    except Exception as e:
        error = e
    finally:
        try:
            os.killpg(child.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        child.wait()
        resv.stop()
    if not os.path.exists(spec["result_path"]):
        raise error or RuntimeError("the replica's process left no report")
    with open(spec["result_path"]) as f:
        report = json.load(f)
    if error is not None or not report.get("ok"):
        report["ok"] = False
        report.setdefault("error", repr(error))
        return report
    report["window"] = dict(loadgen, compiles=report.get("compiles", {
        "backend_compiles": 0, "cache_hits": 0, "cache_misses": 0}))
    report["attempted"] = loadgen.get("requests", 0)
    report["failed"] = loadgen.get("failed", 0)
    problems = report.setdefault("problems", [])
    if loadgen.get("sample_missing"):
        problems.append("sampled requests without a reply: {}".format(
            loadgen["sample_missing"]))
    if loadgen.get("child_exit"):
        problems.append("the replica's process ended with {}".format(
            loadgen["child_exit"]))
    report["model"] = {"latency_limit_ms": traffic["latency_limit_ms"],
                       "max_batch": max(cfg["serve_buckets"])}
    return report


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--child":
        child_main(sys.argv[2])
