"""CI gate: cluster-wide on-demand device profiling.

Boots a 2-node in-process cluster (``cluster.run(..., telemetry=True,
observatory=True, profiler=True)``) whose node fn trains a linear model
through ``Trainer.fit_feed`` and then holds the process alive running small
jitted steps, and asserts the device-plane observability legs:

1. **on-demand capture** — once the trainers' counters have reached
   ``/metrics`` and ``/status`` lists the per-node ``profiler_addresses``,
   ``GET /profile?duration_ms=...`` mid-run answers with a capture id,
   every node's artifacts land under
   ``profiles/<capture_id>/node-<executor>/`` on the driver, and the
   ``capture.json`` manifest carries the metrics snapshot; ``/status``
   reports the capture complete,
2. **one merged timeline** — ``scripts/analyze_profile.py`` merges the
   per-node device traces with the host-side telemetry traces into one
   Chrome-trace JSON containing both device and host events.

Run next to the observatory gate in run_tests.sh.  Exit 0 = a live cluster
hands over its device traces, on demand, from one HTTP endpoint.
"""

import glob
import json
import os
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "scripts"))

os.environ["JAX_PLATFORMS"] = "cpu"  # a CPU gate: several jax processes

READY_DEADLINE_SECS = 60.0
CAPTURE_DEADLINE_SECS = 45.0
HOLD_TIMEOUT_SECS = 90.0   # node-side backstop: never outlive the driver


def _node_fn(args, ctx):
    """Linear fit via fit_feed (its counters ride the heartbeats), then hold
    the process hot until the driver's release file appears so the capture
    has a live node to profile."""
    import os as _os
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu import train as train_mod
    from tensorflowonspark_tpu.parallel import infeed, mesh as mesh_mod

    mesh = mesh_mod.build_mesh()

    def loss(params, batch, mask):
        pred = batch["x"] @ params["w"] + params["b"]
        err = (pred - batch["y"]) ** 2 * mask
        return err.sum() / jnp.maximum(mask.sum(), 1.0), pred

    trainer = train_mod.Trainer(loss, {"w": jnp.zeros((2,)),
                                       "b": jnp.zeros(())},
                                optax.sgd(0.1), mesh=mesh, batch_size=8,
                                log_steps=2)

    def preprocess(items):
        arr = np.asarray(items, np.float32).reshape(-1)
        return {"x": np.stack([arr, arr * 0.5], axis=1), "y": arr * 2.0}

    sharded = infeed.ShardedFeed(ctx.get_data_feed(), mesh,
                                 global_batch_size=8, preprocess=preprocess)
    trainer.fit_feed(sharded)

    # Keep issuing device work while the driver triggers the capture: an
    # idle device yields an empty (but valid) trace; a hot one proves the
    # xplane decoder on real events.
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    deadline = _time.monotonic() + HOLD_TIMEOUT_SECS
    while (_time.monotonic() < deadline
           and not _os.path.exists(args["release_file"])):
        f(x).block_until_ready()
        _time.sleep(0.05)


def _get(base, path, timeout=5):
    return urllib.request.urlopen(base + path, timeout=timeout).read().decode()


def main():
    from tensorflowonspark_tpu import backend, cluster
    from tensorflowonspark_tpu.cluster import InputMode

    tmp = tempfile.mkdtemp(prefix="tfos-profiling-")
    tdir = os.path.join(tmp, "telemetry")
    release_file = os.path.join(tmp, "release")
    b = backend.LocalBackend(2)
    try:
        c = cluster.run(b, _node_fn, tf_args={"release_file": release_file},
                        num_executors=2, input_mode=InputMode.SPARK,
                        # 1s beats (3s liveness tolerance): a capture adds
                        # real CPU work on the nodes, and on a loaded 1-core
                        # CI box the tight 0.5s cadence false-fences a node
                        # whose beat thread gets starved mid-capture
                        log_dir=tmp, heartbeat_interval=1.0,
                        telemetry=True, telemetry_dir=tdir,
                        observatory=True, profiler=True)
        assert c.observatory is not None and c.observatory.addr, \
            "observatory did not start"
        base = "http://%s:%d" % c.observatory.addr
        c.train(backend.partition(range(256), 2))

        # Mid-run: the trainers' counters are on /metrics (so the manifest
        # has a snapshot to carry) and both profiler addresses on /status.
        deadline = time.time() + READY_DEADLINE_SECS
        while time.time() < deadline:
            if "tfos_dispatch_count_total" in _get(base, "/metrics"):
                break
            time.sleep(0.5)
        else:
            raise AssertionError(
                "no tfos_dispatch_count_total on /metrics within %.0fs"
                % READY_DEADLINE_SECS)
        status = json.loads(_get(base, "/status"))
        addrs = status.get("profiler_addresses") or []
        assert len(addrs) == 2 and all(":" in a for a in addrs), \
            "/status profiler_addresses wrong: {}".format(addrs)

        # Leg 1: trigger a capture over the live cluster and wait for both
        # nodes' artifacts to land.
        trig = json.loads(_get(base, "/profile?duration_ms=800"))
        capture_id, capture_dir = trig["capture_id"], trig["dir"]
        assert sorted(trig["targets"]) == ["0", "1"], trig
        deadline = time.time() + CAPTURE_DEADLINE_SECS
        last = None
        while time.time() < deadline:
            last = json.loads(_get(base, "/status")).get("last_capture")
            if last and last.get("complete"):
                break
            time.sleep(0.5)
        assert last and last.get("complete"), \
            "capture {} never completed: {}".format(capture_id, last)
        assert not last.get("errors"), \
            "capture reported node errors: {}".format(last["errors"])
        for ex in (0, 1):
            files = glob.glob(os.path.join(capture_dir,
                                           "node-%d" % ex, "**", "*"),
                              recursive=True)
            assert any(os.path.isfile(p) for p in files), \
                "node %d delivered no artifacts under %s" % (ex, capture_dir)
        with open(os.path.join(capture_dir, "capture.json")) as f:
            manifest = json.load(f)
        assert manifest["capture_id"] == capture_id
        agg = (manifest.get("metrics") or {}).get("aggregate") or {}
        assert agg.get("dispatch_count"), \
            "manifest metrics snapshot lacks the trainers' counters"

        # Release the nodes, then shut down so every telemetry trace
        # flushes before the merge.
        with open(release_file, "w") as f:
            f.write("done")
        c.shutdown(grace_secs=5)
        assert "error" not in c.tf_status, c.tf_status["error"]

        # Leg 2: one merged Perfetto timeline, device + host events.
        import analyze_profile
        merged_path = os.path.join(capture_dir, "merged_timeline.json")
        rc = analyze_profile.main([capture_dir, "--telemetry-dir", tdir,
                                   "--out", merged_path])
        assert rc == 0, "analyze_profile failed with rc=%s" % rc
        with open(merged_path) as f:
            merged = json.load(f)
        events = merged.get("traceEvents") or []
        cats = {e.get("cat") for e in events}
        assert "device" in cats, \
            "merged timeline has no device events (cats: %s)" % sorted(
                x for x in cats if x)
        host_events = [e for e in events
                       if e.get("pid") is not None
                       and e["pid"] < analyze_profile.DEVICE_PID_BASE]
        assert host_events, "merged timeline has no host-side events"

        print("profiling OK: capture {} collected {} node dir(s), merged "
              "timeline has {} events ({} host-side)".format(
                  capture_id, len(manifest.get("nodes") or {}),
                  len(events), len(host_events)))
        return 0
    finally:
        try:
            with open(release_file, "w") as f:
                f.write("done")
        except OSError:
            pass
        b.stop()


if __name__ == "__main__":
    sys.exit(main())
