"""CI gate: the device-resident step loop must actually overlap.

Boots a real 2-node in-process cluster on the built-in backend with
``telemetry=True`` and ``TFOS_TRANSFER_GUARD=disallow`` exported to the
executors, trains a small linear model through the full data plane
(DataFeed -> ShardedFeed -> Trainer.fit_feed), and asserts the three
overlap legs this repo's MFU story depends on:

1. **device residency** — every dispatch runs under
   ``jax.transfer_guard_host_to_device("disallow")``; an implicit
   ``device_put`` sneaking back onto the dispatch path fails the run,
2. **async checkpointing** — a forced ``maybe_save`` whose orbax write is
   artificially slowed (0.4 s) returns in well under that, has NOT landed
   at return time, keeps training (steps complete while the save is in
   flight), and is flushed by ``wait_until_finished``,
3. **overlap telemetry** — the ``dispatch_gap_us`` / ``infeed_*`` counters
   ride heartbeats into ``tf_status["telemetry"]["aggregate"]`` and the
   per-process trace files carry the ``train/dispatch`` /
   ``infeed/device_put`` / ``checkpoint/save`` spans.

Run next to the dataservice gate in run_tests.sh.  Exit 0 = the loop
overlaps; any assertion names the leg that broke.
"""

import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"  # a CPU gate: several jax processes
# Inherited by the executor processes: every fit_feed dispatch in the node
# fn runs under the h2d transfer guard (leg 1).
os.environ["TFOS_TRANSFER_GUARD"] = "disallow"

#: Overlap-specific span/instant names a healthy run must emit somewhere
#: across the per-process trace files.
REQUIRED_EVENTS = (
    "train/dispatch",
    "infeed/device_put",
    "checkpoint/save_requested",
    "checkpoint/save",
)

SAVE_LATENCY_SECS = 0.4   # artificial orbax write latency in the node fn
FAST_RETURN_SECS = 0.25   # maybe_save must return well under SAVE_LATENCY


def _node_fn(args, ctx):
    """Linear-regression fit over the cluster data plane with a slowed
    async checkpoint; records request/landing evidence for the driver."""
    import time

    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu import checkpoint
    from tensorflowonspark_tpu import train as train_mod
    from tensorflowonspark_tpu.parallel import infeed, mesh as mesh_mod

    mesh = mesh_mod.build_mesh()
    params = {"w": jnp.zeros((2,)), "b": jnp.zeros(())}

    def loss(params, batch, mask):
        pred = batch["x"] @ params["w"] + params["b"]
        err = (pred - batch["y"]) ** 2 * mask
        return err.sum() / jnp.maximum(mask.sum(), 1.0), pred

    trainer = train_mod.Trainer(loss, params, optax.sgd(0.1), mesh=mesh,
                                batch_size=8)

    def preprocess(items):
        arr = np.asarray(items, np.float32).reshape(-1)
        return {"x": np.stack([arr, arr * 0.5], axis=1),
                "y": arr * 2.0}

    sharded = infeed.ShardedFeed(ctx.get_data_feed(), mesh,
                                 global_batch_size=8, preprocess=preprocess)

    mgr = checkpoint.CheckpointManager(
        os.path.join(os.getcwd(), "ckpt"),
        save_interval_steps=10000,    # only the forced save below fires
        async_save=True)
    evidence = {}
    progress = {"steps": 0}
    orig_save = mgr._mgr.save

    def slow_save(*a, **kw):
        time.sleep(SAVE_LATENCY_SECS)
        result = orig_save(*a, **kw)
        # Worker thread: how far training got while the write was in flight.
        evidence["steps_when_save_landed"] = progress["steps"]
        return result

    mgr._mgr.save = slow_save

    def on_steps(steps_done):
        progress["steps"] = steps_done
        if steps_done >= 4 and "request_step" not in evidence:
            t0 = time.perf_counter()
            accepted = mgr.maybe_save(steps_done, trainer.state, force=True)
            evidence["request_step"] = steps_done
            evidence["request_secs"] = time.perf_counter() - t0
            evidence["accepted"] = bool(accepted)
            # Raw orbax view, no drain: must still be empty (async).
            evidence["landed_at_request"] = mgr._mgr.latest_step()

    stats = trainer.fit_feed(sharded, on_steps=on_steps)
    mgr.wait_until_finished()
    evidence["final_latest"] = mgr.latest_step()
    evidence["final_steps"] = progress["steps"]
    evidence["overlap"] = stats.get("overlap", {})
    mgr.close()
    with open("overlap.json", "w") as f:
        json.dump(evidence, f)
    # Keep the registered counter sources alive across a few heartbeats so
    # the driver's telemetry aggregate latches the final tallies (leg 3).
    time.sleep(1.5)


def main():
    from tensorflowonspark_tpu import backend, cluster
    from tensorflowonspark_tpu.cluster import InputMode

    tdir = os.path.join(tempfile.mkdtemp(prefix="tfos-overlap-"), "t")
    b = backend.LocalBackend(2)
    try:
        c = cluster.run(b, _node_fn, tf_args=[], num_executors=2,
                        input_mode=InputMode.SPARK,
                        heartbeat_interval=0.5,
                        telemetry=True, telemetry_dir=tdir)
        c.train(backend.partition(range(256), 2))
        c.shutdown(grace_secs=3)
        assert "error" not in c.tf_status, c.tf_status["error"]

        # Legs 1+2: per-executor evidence files.  The run completing at all
        # under TFOS_TRANSFER_GUARD=disallow is the device-residency proof;
        # the recorded timings are the async-save proof.
        for i in (0, 1):
            path = os.path.join(b.workdir_root,
                                "executor-{}".format(i), "overlap.json")
            assert os.path.exists(path), \
                "executor {} wrote no overlap evidence (transfer guard " \
                "trip or crash?)".format(i)
            with open(path) as f:
                ev = json.load(f)
            assert ev.get("accepted"), "save request rejected: {}".format(ev)
            assert ev["request_secs"] < FAST_RETURN_SECS, \
                "maybe_save blocked {:.3f}s (>= {}s): not async".format(
                    ev["request_secs"], FAST_RETURN_SECS)
            assert ev["landed_at_request"] is None, \
                "save already landed when maybe_save returned: {}".format(ev)
            assert ev["final_latest"] == ev["request_step"], \
                "wait_until_finished did not flush the save: {}".format(ev)
            assert ev.get("steps_when_save_landed", 0) >= \
                ev["request_step"], \
                "no training progress while save in flight: {}".format(ev)
            ov = ev.get("overlap", {})
            assert ov.get("dispatch_count", 0) >= 2, \
                "too few dispatches recorded: {}".format(ov)
            assert ov.get("dispatch_gap_us", 0) > 0, \
                "dispatch_gap_us not measured: {}".format(ov)
            assert ov.get("infeed_batches", 0) > 0, \
                "infeed_batches not measured: {}".format(ov)
            assert ov.get("infeed_put_us", 0) > 0, \
                "infeed_put_us not measured: {}".format(ov)

        # Leg 3a: counters rode heartbeats into the driver aggregate.
        tele = c.tf_status.get("telemetry")
        assert tele and tele.get("nodes"), \
            "tf_status['telemetry'] missing or empty: {}".format(tele)
        agg = tele["aggregate"]
        for key in ("dispatch_count", "dispatch_gap_us",
                    "infeed_batches", "infeed_put_us"):
            assert agg.get(key, 0) > 0, \
                "aggregate {} not positive: {}".format(key, agg)

        # Leg 3b: the overlap span vocabulary is in the trace files.
        names = set()
        for path in sorted(glob.glob(os.path.join(tdir, "trace-*.json"))):
            with open(path) as f:
                doc = json.load(f)
            names.update(e.get("name")
                         for e in doc.get("traceEvents") or [])
        missing = [n for n in REQUIRED_EVENTS if n not in names]
        assert not missing, \
            "trace files missing overlap events {}; saw {}".format(
                missing, sorted(n for n in names if n))

        print("overlap OK: guard-clean dispatches, async save returned "
              "<{:.2f}s with {:.1f}s write in flight, aggregate "
              "dispatch_gap_us={} infeed_put_us={}".format(
                  FAST_RETURN_SECS, SAVE_LATENCY_SECS,
                  agg["dispatch_gap_us"], agg["infeed_put_us"]))
        return 0
    finally:
        b.stop()


if __name__ == "__main__":
    sys.exit(main())
