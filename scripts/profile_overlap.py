"""CPU microbench: how much dispatch gap the device-resident step loop
closes.

Runs the SAME linear-model fit twice through the real data plane
(manager -> DataFeed -> ShardedFeed -> Trainer.fit_feed + CheckpointManager)
with a simulated per-batch host assembly cost and a simulated orbax write
latency, and reports the dispatch-gap counters for:

- ``baseline``  — prefetch=0 (transfer on the dispatch path) + synchronous
  checkpoint saves: the pre-change loop shape,
- ``overlapped`` — prefetch=2 (transfer in the prefetch thread) + async
  saves: the shipped defaults.

Host counts of a CPU box, no device rate.  Pure stdlib + repo deps; CPU
only; ~10 s.  Usage::

    python scripts/profile_overlap.py [--steps 60]
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"  # a CPU gate: several jax processes

ASSEMBLY_COST_SECS = 0.004   # simulated host-side feature assembly per batch
SAVE_LATENCY_SECS = 0.15     # simulated orbax serialization+write per save
SAVE_EVERY_STEPS = 10
BATCH = 8


def run_config(name, prefetch, async_save, steps):
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu import checkpoint, manager
    from tensorflowonspark_tpu.datafeed import DataFeed
    from tensorflowonspark_tpu.parallel import build_mesh
    from tensorflowonspark_tpu.parallel.infeed import ShardedFeed
    from tensorflowonspark_tpu.train import Trainer

    m = manager.start(b"profile-overlap", ["input", "output", "error"])
    try:
        q = m.get_queue("input")
        for i in range(steps * BATCH):
            q.put([float(i % 7), float(i % 5), float(i % 3)])
        q.put(None)

        def preprocess(items):
            time.sleep(ASSEMBLY_COST_SECS)  # stand-in for real featurization
            arr = np.asarray(items, np.float32)
            return {"x": arr[:, :2], "y": arr[:, 2]}

        def loss(params, batch, mask):
            pred = batch["x"] @ params["w"] + params["b"]
            err = (pred - batch["y"]) ** 2 * mask
            return err.sum() / jnp.maximum(mask.sum(), 1.0), pred

        mesh = build_mesh()
        sharded = ShardedFeed(DataFeed(m), mesh, global_batch_size=BATCH,
                              prefetch=prefetch, preprocess=preprocess)
        params = {"w": jnp.zeros((2,)), "b": jnp.zeros(())}
        trainer = Trainer(loss, params, optax.sgd(0.01), mesh=mesh,
                          batch_size=BATCH)
        ckpt = checkpoint.CheckpointManager(
            tempfile.mkdtemp(prefix="profile-overlap-"),
            save_interval_steps=SAVE_EVERY_STEPS, async_save=async_save)
        orig_save = ckpt._mgr.save

        def slow_save(*a, **kw):
            time.sleep(SAVE_LATENCY_SECS)
            return orig_save(*a, **kw)

        ckpt._mgr.save = slow_save

        # Warm the jit caches OUTSIDE the measured window so compile time
        # doesn't masquerade as dispatch gap in either configuration.
        warm = {"x": np.zeros((BATCH, 2), np.float32),
                "y": np.zeros((BATCH,), np.float32)}
        trainer.step(sharded._shard(warm, BATCH)[0])

        t0 = time.perf_counter()
        stats = trainer.fit_feed(
            sharded, on_steps=lambda s: ckpt.maybe_save(s, trainer.state))
        ckpt.wait_until_finished()
        wall = time.perf_counter() - t0
        ckpt.close()

        ov = stats["overlap"]
        disp = max(ov.get("dispatch_count", 0), 1)
        nb = max(ov.get("infeed_batches", 0), 1)
        return {
            "config": name,
            "prefetch": prefetch,
            "async_save": async_save,
            "steps": ov.get("dispatch_count"),
            "wall_secs": round(wall, 3),
            "dispatch_gap_us_avg": round(ov.get("dispatch_gap_us", 0) / disp, 1),
            "dispatch_gap_us_hwm": ov.get("dispatch_gap_us_hwm"),
            "infeed_assembly_us_avg": round(
                ov.get("infeed_assembly_us", 0) / nb, 1),
            "infeed_put_us_avg": round(ov.get("infeed_put_us", 0) / nb, 1),
        }
    finally:
        m.shutdown()


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args()

    baseline = run_config("baseline", prefetch=0, async_save=False,
                          steps=args.steps)
    overlapped = run_config("overlapped", prefetch=2, async_save=True,
                            steps=args.steps)
    gap_closed = 0.0
    if baseline["dispatch_gap_us_avg"]:
        gap_closed = 1 - (overlapped["dispatch_gap_us_avg"]
                          / baseline["dispatch_gap_us_avg"])
    out = {
        "assembly_cost_us": int(ASSEMBLY_COST_SECS * 1e6),
        "save_latency_ms": int(SAVE_LATENCY_SECS * 1e3),
        "save_every_steps": SAVE_EVERY_STEPS,
        "baseline": baseline,
        "overlapped": overlapped,
        "dispatch_gap_closed_pct": round(gap_closed * 100, 1),
        "wall_speedup": round(baseline["wall_secs"]
                              / max(overlapped["wall_secs"], 1e-9), 2),
    }
    print(json.dumps(out, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
