"""CI gate: the megastep engine must actually amortize host work.

Runs the SAME linear-regression fit through the full cluster data plane
(DataFeed -> ShardedFeed -> Trainer.fit_feed) twice, on two fresh 2-node
in-process clusters with ``TFOS_TRANSFER_GUARD=disallow`` exported to the
executors:

1. **single-step baseline** — ``steps_per_call=1``, one dispatch per batch,
2. **grouped megastep run** — ``TFOS_STEPS_PER_CALL=4`` in the executor
   env (the fit_feed default path, not a caller argument), with a LIVE
   mid-run retune: once 8 steps are done the on_steps hook pushes
   ``train_steps_per_call=8`` through ``node.apply_knobs`` exactly like an
   autopilot KNOB heartbeat reply would.

and asserts the four legs the round-15 perf story depends on:

- **exact work, exact boundaries** — both runs train every row exactly
  once (steps x batch == rows); every grouped dispatch lands on a group
  boundary (step deltas are whole groups of the K armed at fill time:
  4 before the push, 8 after, degrade-singles of 1 only at the tail —
  never a partial group), and the ``train_steps_per_call_max`` gauge
  confirms the retune reached the dispatch path,
- **device-side assembly** — the grouped run completes under the d2h+h2d
  transfer guard with ``train_group_assemble_us`` > 0: stacks are built
  by the jitted device assembler, not host np.stack round-trips,
- **host amortization** — measured on the WARM dispatch path with
  device-resident data (the cluster feed's between-dispatch gap is
  production-dominated on the CPU rig — manager-queue row transport —
  and would hide the effect): host+dispatch wall per step through
  ``multi_step(K=8)`` must be measurably below ``step()``'s, i.e. the
  per-dispatch Python/runtime/bookkeeping cost is actually paid once
  per K steps,
- **donated stacks** — the grouped stats stamp
  ``megastep.donate_batches=True`` (device assembly + donating trainer).

Run next to the overlap gate in run_tests.sh.  Exit 0 = the megastep
engine amortizes; any assertion names the leg that broke.
"""

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"  # a CPU gate: several jax processes
# Inherited by every executor: all dispatches in both phases run under the
# transfer guard — a host round-trip on the grouped path fails the run.
os.environ["TFOS_TRANSFER_GUARD"] = "disallow"

ROWS = 512            # per cluster; 2 executors x 256 rows
GLOBAL_BATCH = 8      # each executor is its own 1-process jax world:
                      # 256 rows / 8 -> 32 steps per executor per phase
RETUNE_AT = 8         # grouped phase: push K=8 after this many steps
#: warm-path wall per step via multi_step(K=8) must be below this fraction
#: of step()'s.  The measured CPU-rig ratio is well under 0.5 (PERF.md
#: round 15); 0.75 leaves headroom for CI noise while still failing a
#: regression that un-amortizes the dispatch path.
AMORTIZE_RATIO_MAX = 0.75
MICRO_STEPS = 64      # resident-batch steps timed per mode


def _node_fn(args, ctx):
    """Linear fit over the cluster data plane; grouped phase (detected via
    TFOS_STEPS_PER_CALL) live-retunes K mid-run through node.apply_knobs."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu import node as node_mod
    from tensorflowonspark_tpu import train as train_mod
    from tensorflowonspark_tpu.parallel import infeed, mesh as mesh_mod

    mesh = mesh_mod.build_mesh()
    params = {"w": jnp.zeros((2,)), "b": jnp.zeros(())}

    def loss(params, batch, mask):
        pred = batch["x"] @ params["w"] + params["b"]
        err = (pred - batch["y"]) ** 2 * mask
        return err.sum() / jnp.maximum(mask.sum(), 1.0), pred

    trainer = train_mod.Trainer(loss, params, optax.sgd(0.1), mesh=mesh,
                                batch_size=GLOBAL_BATCH)

    def preprocess(items):
        # normalized to [0, 1): raw row ids up to 512 diverge sgd(0.1)
        arr = np.asarray(items, np.float32).reshape(-1) / 512.0
        return {"x": np.stack([arr, arr * 0.5], axis=1),
                "y": arr * 2.0}

    sharded = infeed.ShardedFeed(ctx.get_data_feed(), mesh,
                                 global_batch_size=GLOBAL_BATCH,
                                 preprocess=preprocess)

    grouped = bool(os.environ.get("TFOS_STEPS_PER_CALL"))
    seen = []

    def on_steps(steps_done):
        seen.append(steps_done)
        if grouped and steps_done >= RETUNE_AT and \
                not getattr(on_steps, "pushed", False):
            # the autopilot actuation path, minus the heartbeat transport
            on_steps.pushed = node_mod.apply_knobs(
                {"train_steps_per_call": 8}) > 0

    stats = trainer.fit_feed(sharded, on_steps=on_steps)
    snap = dict(trainer.counters_snapshot())
    snap.update(sharded.counters_snapshot())
    evidence = {
        "global_steps": stats["global_steps"],
        "deltas": [b - a for a, b in zip([0] + seen, seen)],
        "megastep": stats.get("megastep", {}),
        "overlap": stats.get("overlap", {}),
        "counters": snap,
        "retune_pushed": bool(getattr(on_steps, "pushed", False)),
    }
    if grouped:
        evidence.update(_amortization_microbench(trainer, mesh))
    with open("megastep.json", "w") as f:
        json.dump(evidence, f)


def _amortization_microbench(trainer, mesh):
    """Time MICRO_STEPS warm steps on device-resident data, once through
    the single-step path and once through multi_step(K=8) with fresh
    donated stacks per call.  Same math either way, so the wall delta IS
    the amortized per-dispatch host overhead."""
    import time as _time

    import jax
    import numpy as np

    from tensorflowonspark_tpu.parallel import mesh as mesh_mod

    k = 8
    rng = np.random.RandomState(0)
    batch_sh = mesh_mod.batch_sharding(mesh)
    scan_sh = mesh_mod.scan_batch_sharding(mesh)
    x = rng.rand(GLOBAL_BATCH, 2).astype(np.float32)
    batch = {"x": jax.device_put(x, batch_sh),
             "y": jax.device_put(x[:, 0] * 2.0, batch_sh)}

    def fresh_stack():
        xs = rng.rand(k, GLOBAL_BATCH, 2).astype(np.float32)
        return ({"x": jax.device_put(xs, scan_sh),
                 "y": jax.device_put(xs[:, :, 0] * 2.0, scan_sh)},
                jax.device_put(np.ones((k, GLOBAL_BATCH), np.float32),
                               scan_sh))

    # warm both programs outside the timed region
    trainer.step(batch)
    trainer.multi_step(*fresh_stack(), donate_batches=True)

    t0 = _time.perf_counter()
    for _ in range(MICRO_STEPS):
        loss, _ = trainer.step(batch)
    jax.block_until_ready(loss)
    us_single = (_time.perf_counter() - t0) * 1e6 / MICRO_STEPS

    stacks = [fresh_stack() for _ in range(MICRO_STEPS // k)]
    t0 = _time.perf_counter()
    for bm in stacks:
        final = trainer.multi_step(*bm, donate_batches=True)
    jax.block_until_ready(final)
    us_multi = (_time.perf_counter() - t0) * 1e6 / MICRO_STEPS
    return {"us_per_step_single": us_single, "us_per_step_multi": us_multi}


def _run_phase(extra_env):
    from tensorflowonspark_tpu import backend, cluster
    from tensorflowonspark_tpu.cluster import InputMode

    b = backend.LocalBackend(2, env=extra_env)
    try:
        c = cluster.run(b, _node_fn, tf_args=[], num_executors=2,
                        input_mode=InputMode.SPARK,
                        heartbeat_interval=0.5)
        c.train(backend.partition(range(ROWS), 2))
        c.shutdown(grace_secs=3)
        assert "error" not in c.tf_status, c.tf_status["error"]
        out = []
        for i in (0, 1):
            path = os.path.join(b.workdir_root,
                                "executor-{}".format(i), "megastep.json")
            assert os.path.exists(path), \
                "executor {} wrote no megastep evidence (transfer guard " \
                "trip or crash?)".format(i)
            with open(path) as f:
                out.append(json.load(f))
        return out
    finally:
        b.stop()


def _gap_per_step(ev):
    ov = ev["overlap"]
    return ov.get("dispatch_gap_us", 0) / max(ev["global_steps"], 1)


def main():
    steps = ROWS // 2 // GLOBAL_BATCH   # per executor

    single = _run_phase({})
    grouped = _run_phase({"TFOS_STEPS_PER_CALL": "4"})

    for ev in single:
        assert ev["global_steps"] == steps, \
            "single phase lost steps: {}".format(ev["global_steps"])
        assert all(d == 1 for d in ev["deltas"]), ev["deltas"]
        assert ev["megastep"]["steps_per_call"] == 1, ev["megastep"]

    for ev in grouped:
        # exact work: every row trained exactly once
        assert ev["global_steps"] == steps, \
            "grouped phase lost steps: {}".format(ev["global_steps"])
        mega = ev["megastep"]
        assert mega["steps_per_call"] == 4, \
            "executor env K did not reach fit_feed: {}".format(mega)
        assert mega["group_assembly"] == "device", mega
        assert mega["donate_batches"] is True, mega
        # boundary landing: whole groups only — K=4 before the push, K=8
        # after, degrade-singles at the tail; a 2/3/5/6/7 delta means a
        # retune tore a group
        deltas = ev["deltas"]
        assert deltas[0] == 4, \
            "first dispatch not a K=4 group: {}".format(deltas)
        assert set(deltas) <= {1, 4, 8}, \
            "partial group dispatched (retune off-boundary): {}".format(
                deltas)
        assert ev["retune_pushed"], "apply_knobs claimed nothing"
        assert 8 in deltas, \
            "live K=8 retune never reached a dispatch: {}".format(deltas)
        # the gauge rode the counters: the dispatch path really armed K=8
        assert ev["counters"].get("train_steps_per_call_max") == 8, \
            ev["counters"]
        assert ev["counters"].get("train_steps_total") == steps, \
            ev["counters"]
        # device-side assembly did the stacking (guard-clean + tallied)
        assert ev["counters"].get("train_group_assemble_us", 0) > 0, \
            ev["counters"]

    # host amortization: warm resident-batch dispatch path, worst executor
    worst = max(grouped,
                key=lambda ev: ev["us_per_step_multi"] /
                max(ev["us_per_step_single"], 1e-9))
    us_single = worst["us_per_step_single"]
    us_multi = worst["us_per_step_multi"]
    assert us_single > 0, "microbench measured nothing"
    assert us_multi < AMORTIZE_RATIO_MAX * us_single, \
        "megastep did not amortize host work: multi_step(8) {:.0f}us/step " \
        "vs step() {:.0f}us/step (need < {:.0%})".format(
            us_multi, us_single, AMORTIZE_RATIO_MAX)

    gap_single = max(_gap_per_step(ev) for ev in single)
    gap_grouped = max(_gap_per_step(ev) for ev in grouped)
    print("megastep OK: guard-clean K=4 groups with live K=8 retune on a "
          "group boundary (deltas {}), warm host+dispatch {:.0f} -> {:.0f} "
          "us per step (feed-gap {:.0f} -> {:.0f} us/step, "
          "production-bound)".format(grouped[0]["deltas"], us_single,
                                     us_multi, gap_single, gap_grouped))
    return 0


if __name__ == "__main__":
    sys.exit(main())
