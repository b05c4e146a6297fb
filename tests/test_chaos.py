"""Chaos tests: FaultInjector-driven failures against REAL clusters, proving
the full detect → retry → recover loop (the acceptance path for the
fault-tolerance subsystem).

Every test here runs under the ``chaos`` marker's SIGALRM wall-clock limit
(see ``conftest.py``): a broken recovery path presents as a hang, and the
alarm turns that into a stack-bearing failure instead of a stuck suite.
"""

import glob
import json
import os
import random
import time

import numpy as np
import pytest

from tensorflowonspark_tpu import backend, cluster, fault
from tensorflowonspark_tpu.cluster import InputMode


def _node_sum_fn(args, ctx):
    """Consume this node's feed and persist the running total; the injector
    (planted via env on exactly one executor) kills the node mid-consumption."""
    feed = ctx.get_data_feed()
    total = 0
    while not feed.should_stop():
        for x in feed.next_batch(2):
            total += x
    with open("sum.txt", "w") as f:
        f.write(str(total))


@pytest.mark.chaos(timeout=180)
def test_node_killed_mid_feed_is_detected_and_retried():
    """The flagship end-to-end: SIGKILL one node mid-feed via FaultInjector →
    the liveness monitor declares it dead within the missed-beat deadline
    (seconds, not the 600s feed timeout) and fences its executor → the
    supervised feed job retries the failed partition with backoff onto the
    surviving executor → its node consumes the retried partition and the run
    completes with the full dataset accounted for."""
    spec = json.dumps({"kill_after_items": 5})
    b = backend.LocalBackend(
        2, env_per_executor=[{fault.FAULT_SPEC_ENV: spec}, None])
    try:
        c = cluster.run(b, _node_sum_fn, tf_args=[], num_executors=2,
                        input_mode=InputMode.SPARK,
                        heartbeat_interval=0.5, heartbeat_misses=2)
        policy = fault.RetryPolicy(max_attempts=5, initial_backoff=1.5,
                                   multiplier=1.5, jitter=0.3,
                                   rng=random.Random(7))
        t0 = time.time()
        c.train(backend.partition(range(20), 2), retry_policy=policy)
        elapsed = time.time() - t0
        # recovery, not the feeder's 600s drain timeout, resolved the death
        assert elapsed < 90, elapsed
        # the liveness monitor (not the feed plane) identified WHO died
        dead = c.tf_status.get("dead_nodes")
        assert dead and "executor 0" in dead[0], c.tf_status
        # a recovered run is a SUCCESS: no fatal latch, clean exit 0
        assert "error" not in c.tf_status
        c.shutdown(grace_secs=1)
        # The surviving node consumed its own partition AND the retried one:
        # nothing of the dataset was lost with the dead node.
        with open(os.path.join(b.workdir_root, "executor-1",
                               "sum.txt")) as f:
            assert int(f.read()) == sum(range(20))
        # the killed node never completed (its partial file must not exist)
        assert not os.path.exists(
            os.path.join(b.workdir_root, "executor-0", "sum.txt"))
    finally:
        b.stop()


@pytest.mark.chaos(timeout=120)
def test_injected_user_failure_stays_fatal_despite_retry_policy():
    """A user-code failure under a retry policy must raise immediately —
    retrying would re-train on duplicate rows (the classification contract)."""
    spec = json.dumps({"fail_after_items": 3,
                       "message": "injected consumer bug"})
    b = backend.LocalBackend(
        2, env_per_executor=[{fault.FAULT_SPEC_ENV: spec}, None])
    try:
        c = cluster.run(b, _node_sum_fn, tf_args=[], num_executors=2,
                        input_mode=InputMode.SPARK)
        policy = fault.RetryPolicy(max_attempts=4, initial_backoff=0.1)
        t0 = time.time()
        with pytest.raises(Exception, match="injected consumer bug"):
            c.train(backend.partition(range(20), 2), feed_timeout=30,
                    retry_policy=policy)
        # one attempt, no backoff ladder: fatal means fatal
        assert time.time() - t0 < 25
        with pytest.raises(SystemExit):
            c.shutdown(grace_secs=1)
    finally:
        b.stop()


class _CrashOnceFeed(object):
    """Feed wrapper that raises an (opt-in retryable) InjectedFailure after
    N batches — a feed-plane loss mid-training."""

    def __init__(self, inner, crash_after):
        self._inner = inner
        self._crash_after = crash_after

    def batches(self):
        for i, item in enumerate(self._inner.batches()):
            if self._crash_after is not None and i >= self._crash_after:
                self._inner.terminate()
                fault.fail("injected feed-plane loss")
            yield item

    def terminate(self):
        self._inner.terminate()


@pytest.mark.chaos(timeout=120)
def test_fit_supervised_restores_latest_and_completes(tmp_path):
    """Supervised trainer restart: crash after step 2 of attempt 1 → the
    supervisor backs off, restores the step-2 checkpoint, and attempt 2
    finishes the run from there (the reference's "Spark retries the job and
    TF restores from the last checkpoint" story, SURVEY §5.3)."""
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu import checkpoint as ckpt_mod
    from tensorflowonspark_tpu import manager
    from tensorflowonspark_tpu.datafeed import DataFeed
    from tensorflowonspark_tpu.parallel import build_mesh
    from tensorflowonspark_tpu.parallel.infeed import ShardedFeed
    from tensorflowonspark_tpu.train import Trainer, fit_supervised

    mesh = build_mesh()
    rng = np.random.RandomState(0)
    rows = [([float(x) for x in rng.rand(2)],) for _ in range(32)]
    rows = [(r[0], float(np.dot(r[0], [3.14, 1.618]))) for r in rows]

    managers, attempts = [], []

    def feed_factory():
        # a FRESH feed per attempt: a crashed consumer's queue state is
        # undefined, so supervision owns feed construction (train.py doc)
        m = manager.start(b"chaos-fit-%d" % len(managers),
                          ["input", "output", "error"])
        managers.append(m)
        q = m.get_queue("input")
        for r in rows:
            q.put(r)
        q.put(None)
        feed = DataFeed(m, input_mapping={"a_x": "x", "b_y": "y"})
        sharded = ShardedFeed(feed, mesh, global_batch_size=8, prefetch=0)
        attempts.append(1)
        # only the first attempt crashes (after 2 of its 4 batches)
        return _CrashOnceFeed(sharded, 2 if len(attempts) == 1 else None)

    def loss(params, batch, mask):
        pred = jnp.asarray(batch["x"]) @ params["w"]
        err = (pred - jnp.asarray(batch["y"])) ** 2 * mask
        return err.sum() / jnp.maximum(mask.sum(), 1.0), {}

    trainer = Trainer(loss, {"w": jnp.zeros((2,))}, optax.sgd(0.05),
                      mesh=mesh, batch_size=8, log_steps=2)
    ckpt = ckpt_mod.CheckpointManager(str(tmp_path / "ckpt"),
                                      save_interval_steps=1)
    policy = fault.RetryPolicy(max_attempts=3, initial_backoff=0.05,
                               extra_retryable=["injected"])
    try:
        stats = fit_supervised(trainer, feed_factory, ckpt,
                               retry_policy=policy)
        assert len(attempts) == 2                     # crashed once, recovered
        # attempt 1 trained steps 1-2 (checkpointed), attempt 2 restored at
        # step 2 and consumed its full fresh feed: 4 more steps
        assert int(trainer.state.step) == 6
        assert ckpt.latest_step() == 6
        assert "loss" in stats
    finally:
        ckpt.close()
        for m in managers:
            m.shutdown()


@pytest.mark.chaos(timeout=120)
def test_fit_supervised_fatal_error_raises_without_retry(tmp_path):
    """A non-retryable failure inside the supervised loop re-raises on the
    first attempt (no silent retry ladder around user bugs)."""
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu import checkpoint as ckpt_mod
    from tensorflowonspark_tpu.train import Trainer, fit_supervised

    calls = []

    def feed_factory():
        calls.append(1)
        raise ValueError("user bug in feed construction")

    trainer = Trainer(lambda p, b, m: (jnp.zeros(()), {}),
                      {"w": jnp.zeros((2,))}, optax.sgd(0.1))
    ckpt = ckpt_mod.CheckpointManager(str(tmp_path / "ckpt"))
    try:
        with pytest.raises(ValueError, match="user bug"):
            fit_supervised(trainer, feed_factory, ckpt,
                           retry_policy=fault.RetryPolicy(
                               max_attempts=5, initial_backoff=0.05))
        assert len(calls) == 1
    finally:
        ckpt.close()


# ---------------------------------------------------------------------------
# elastic recovery
# ---------------------------------------------------------------------------

@pytest.mark.chaos(timeout=240)
def test_elastic_replacement_full_loop():
    """The elastic flagship: SIGKILL one node mid-feed → the liveness monitor
    fences it and RELEASES its roster slot → the backend provisions a fresh
    executor whose start task claims the slot under a bumped generation →
    the supervised retry waits for the admission and re-dispatches the
    failed partition onto the refreshed roster → the run completes with
    every partition fed exactly once, matching an uninterrupted run."""
    spec = json.dumps({"kill_after_items": 5})
    b = backend.LocalBackend(
        3, env_per_executor=[{fault.FAULT_SPEC_ENV: spec}, None, None])
    try:
        c = cluster.run(b, _node_sum_fn, tf_args=[], num_executors=3,
                        input_mode=InputMode.SPARK,
                        heartbeat_interval=0.5, heartbeat_misses=2)
        policy = fault.RetryPolicy(max_attempts=5, initial_backoff=1.5,
                                   multiplier=1.5, jitter=0.3,
                                   rng=random.Random(11))
        c.train(backend.partition(range(30), 3), retry_policy=policy)
        # the death was detected and named...
        dead = c.tf_status.get("dead_nodes")
        assert dead and "executor 0" in dead[0], c.tf_status
        # ...its slot was reclaimed by a replacement under a new generation...
        assert c.tf_status.get("replacements"), c.tf_status
        assert "executor 3 replaces 0" in c.tf_status["replacements"][0]
        assert "replacement_errors" not in c.tf_status, c.tf_status
        assert c.server.reservations.generation >= 1
        roster_ids = sorted(n["executor_id"] for n in c.cluster_info)
        assert roster_ids == [1, 2, 3], c.cluster_info
        # ...and the run is a SUCCESS, not a shrunken survivor crawl
        assert "error" not in c.tf_status
        c.shutdown(grace_secs=1)
        # every partition fed exactly once: totals across the survivors AND
        # the replacement equal the uninterrupted run's total
        total = 0
        for i in (1, 2, 3):
            path = os.path.join(b.workdir_root, "executor-{}".format(i),
                                "sum.txt")
            if os.path.exists(path):
                with open(path) as f:
                    total += int(f.read())
        assert total == sum(range(30))
        # the killed node never completed
        assert not os.path.exists(
            os.path.join(b.workdir_root, "executor-0", "sum.txt"))
    finally:
        b.stop()


def _chaos_timeline_run(tdir):
    """One elastic run with ``telemetry=True`` and executor 0 killed after
    five items; returns every process's trace events by name."""
    spec = json.dumps({"kill_after_items": 5})
    b = backend.LocalBackend(
        3, env_per_executor=[{fault.FAULT_SPEC_ENV: spec}, None, None])
    try:
        c = cluster.run(b, _node_sum_fn, tf_args=[], num_executors=3,
                        input_mode=InputMode.SPARK,
                        heartbeat_interval=0.5, heartbeat_misses=2,
                        telemetry=True, telemetry_dir=tdir)
        policy = fault.RetryPolicy(max_attempts=5, initial_backoff=1.5,
                                   multiplier=1.5, jitter=0.3,
                                   rng=random.Random(13))
        c.train(backend.partition(range(30), 3), retry_policy=policy)
        assert c.tf_status.get("replacements"), c.tf_status
        c.shutdown(grace_secs=1)
    finally:
        b.stop()
    # every process wrote a parseable Chrome trace
    by_name = {}
    for path in glob.glob(os.path.join(tdir, "trace-*.json")):
        with open(path) as f:
            for e in json.load(f)["traceEvents"]:
                by_name.setdefault(e["name"], []).append(e)
    return by_name


@pytest.mark.chaos(timeout=240)
def test_chaos_timeline_reconstructs_kill_fence_reclaim_replace(tmp_path):
    """Observability flagship: rerun the elastic loop with ``telemetry=True``
    and reconstruct the WHOLE incident from the trace files alone —
    injected kill → liveness fence → slot release → replacement admission —
    with consistent executor/generation attributes and causal ordering.
    This is what an operator gets when they load a chaos run's telemetry
    directory into Perfetto."""
    def of_executor(name, executor_id):
        return [e for e in by_name.get(name, [])
                if e["args"].get("executor_id") == executor_id]

    for attempt in range(3):
        by_name = _chaos_timeline_run(str(tmp_path / ("telemetry-%d"
                                                      % attempt)))
        # One second of silence fences a node, and on a loaded machine a
        # healthy one is silent that long: where that happened to executor 0
        # itself before its kill fired, the run holds no injected incident
        # to reconstruct (the kill hit a node already replaced), and the
        # scenario is run again.  A healthy PEER fenced beside it is fine:
        # that is a second story in the same files.
        kills = by_name.get("fault/kill_after_items", [])
        early = [f for f in of_executor("reservation/fence", 0)
                 for k in kills if f["ts"] < k["ts"]]
        if kills and not early:
            break
    # the injected kill itself is on the timeline (the injector flushes
    # its trace before SIGKILLing the process)
    (kill,) = by_name["fault/kill_after_items"]
    assert kill["args"]["items"] >= 5

    # fence -> release -> admission, all naming the same incident: the
    # one injected, executor 0's.  (Under load, one second of silence
    # can fence a healthy node too, and that node gets a story of its
    # own; the assertions below hold this one to exactly one of each.)
    (fence,) = of_executor("reservation/fence", 0)
    (release,) = of_executor("reservation/release", 0)
    assert release["args"]["job_name"] == fence["args"]["job_name"]
    assert release["args"]["task_index"] == fence["args"]["task_index"]
    # the driver dispatched exactly one replacement for it, and names it
    (dispatched,) = [e for e in by_name["cluster/replacement_dispatched"]
                     if e["args"]["dead_executor"] == 0]
    new_id = dispatched["args"]["new_executor"]
    assert new_id >= 3 and (new_id == 3 or len(
        by_name["cluster/replacement_dispatched"]) > 1)
    replacements = [e for e in by_name["reservation/admission"]
                    if e["args"].get("replacement")]
    (admission,) = [e for e in replacements
                    if e["args"]["executor_id"] == new_id]
    adm = admission["args"]
    assert (adm["job_name"], adm["task_index"]) == (
        release["args"]["job_name"], release["args"]["task_index"])
    # the admission bumped the generation the release was observed at
    # (each replacement admitted in between bumped it once more)
    between = [e for e in replacements if e is not admission
               and release["ts"] <= e["ts"] <= admission["ts"]]
    assert adm["generation"] == (release["args"]["generation"] + 1
                                 + len(between))

    # causal order on the shared wall-clock timeline
    assert (kill["ts"] <= fence["ts"] <= release["ts"]
            <= admission["ts"])
    assert release["ts"] <= dispatched["ts"]

    # the driver's replacement dispatch and the new node's bring-up are
    # also present (the "replace" leg of the story)
    assert by_name.get("backend/provision_replacement")
    assert of_executor("node/register", new_id), by_name["node/register"]


@pytest.mark.chaos(timeout=180)
def test_preemption_sigterm_drains_cleanly():
    """Preemption drain e2e: SIGTERM one node mid-feed → its SIGTERM handler
    stops feed consumption and exits cleanly with BYE reason=preempted —
    NO heartbeat-timeout death, no failed feed task, no fatal latch."""
    spec = json.dumps({"sigterm_at_item": 3})
    b = backend.LocalBackend(
        2, env_per_executor=[{fault.FAULT_SPEC_ENV: spec}, None])
    try:
        c = cluster.run(b, _node_sum_fn, tf_args=[], num_executors=2,
                        input_mode=InputMode.SPARK,
                        heartbeat_interval=0.5, heartbeat_misses=2)
        c.train(backend.partition(range(20), 2), feed_timeout=60)
        # the preempted node deregistered CLEANLY: reason surfaced, and its
        # silence was never declared a death
        deadline = time.time() + 10
        while (c.tf_status.get("byes", {}).get("0") != "preempted"
               and time.time() < deadline):
            time.sleep(0.1)
        assert c.tf_status.get("byes", {}).get("0") == "preempted", c.tf_status
        assert not c.tf_status.get("dead_nodes"), c.tf_status
        assert "error" not in c.tf_status
        c.shutdown(grace_secs=1)
        # the survivor finished its work normally
        with open(os.path.join(b.workdir_root, "executor-1",
                               "sum.txt")) as f:
            int(f.read())  # parses: the node completed and persisted
    finally:
        b.stop()


@pytest.mark.chaos(timeout=120)
def test_preemption_emergency_checkpoint_then_resume(tmp_path):
    """Preemption mid-training: the SIGTERM drain runs fit_supervised's
    emergency save (force=True, past the interval gate), the process unwinds
    with SystemExit(0), and a later fit_supervised resumes from the
    emergency step — no training progress lost to the preemption."""
    import signal as signal_mod

    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu import checkpoint as ckpt_mod
    from tensorflowonspark_tpu import manager
    from tensorflowonspark_tpu import node as node_mod
    from tensorflowonspark_tpu.datafeed import DataFeed
    from tensorflowonspark_tpu.parallel import build_mesh
    from tensorflowonspark_tpu.parallel.infeed import ShardedFeed
    from tensorflowonspark_tpu.train import Trainer, fit_supervised

    mesh = build_mesh()
    rng = np.random.RandomState(1)
    rows = [([float(x) for x in rng.rand(2)],) for _ in range(32)]
    rows = [(r[0], float(np.dot(r[0], [2.0, -1.0]))) for r in rows]

    class _PreemptOnceFeed(object):
        """SIGTERMs our own process after N batches; the installed drain
        handler then runs the emergency save and raises SystemExit here."""

        def __init__(self, inner, preempt_after):
            self._inner = inner
            self._preempt_after = preempt_after

        def batches(self):
            for i, item in enumerate(self._inner.batches()):
                if (self._preempt_after is not None
                        and i >= self._preempt_after):
                    os.kill(os.getpid(), signal_mod.SIGTERM)
                yield item

        def terminate(self):
            self._inner.terminate()

    managers = []

    def make_feed_factory(preempt_after):
        def feed_factory():
            m = manager.start(b"chaos-preempt-%d" % len(managers),
                              ["input", "output", "error"])
            managers.append(m)
            q = m.get_queue("input")
            for r in rows:
                q.put(r)
            q.put(None)
            feed = DataFeed(m, input_mapping={"a_x": "x", "b_y": "y"})
            sharded = ShardedFeed(feed, mesh, global_batch_size=8, prefetch=0)
            return _PreemptOnceFeed(sharded, preempt_after)
        return feed_factory

    def loss(params, batch, mask):
        pred = jnp.asarray(batch["x"]) @ params["w"]
        err = (pred - jnp.asarray(batch["y"])) ** 2 * mask
        return err.sum() / jnp.maximum(mask.sum(), 1.0), {}

    # interval 100 >> run length: ONLY the emergency save can land a step
    ckpt = ckpt_mod.CheckpointManager(str(tmp_path / "ckpt"),
                                      save_interval_steps=100)
    old_handler = signal_mod.getsignal(signal_mod.SIGTERM)
    try:
        node_mod._reset_preemption()
        assert node_mod._install_sigterm_drain()
        trainer = Trainer(loss, {"w": jnp.zeros((2,))}, optax.sgd(0.05),
                          mesh=mesh, batch_size=8, log_steps=2)
        with pytest.raises(SystemExit):
            fit_supervised(trainer, make_feed_factory(2), ckpt,
                           retry_policy=fault.RetryPolicy(max_attempts=2))
        assert node_mod.preempted()
        # the emergency save landed the preempted step (interval gate bypassed)
        assert ckpt.latest_step() == 2
        # fit_supervised unregistered its drain callback on the way out
        assert not node_mod._preempt_callbacks

        # --- the replacement run: restore from the emergency step ----------
        node_mod._reset_preemption()
        trainer2 = Trainer(loss, {"w": jnp.zeros((2,))}, optax.sgd(0.05),
                           mesh=mesh, batch_size=8, log_steps=2)
        stats = fit_supervised(trainer2, make_feed_factory(None), ckpt,
                               retry_policy=fault.RetryPolicy(max_attempts=2))
        # resumed at 2, consumed the fresh 4-batch feed: 6 total
        assert int(trainer2.state.step) == 6
        assert ckpt.latest_step() == 6
        assert "loss" in stats
    finally:
        signal_mod.signal(signal_mod.SIGTERM, old_handler)
        node_mod._reset_preemption()
        ckpt.close()
        for m in managers:
            m.shutdown()
