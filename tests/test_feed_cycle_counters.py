"""The Spark feed cycle accounted from inside: the feeder's phase clock
(``node.train``'s task closure, ``_feed_blocks``, ``_ChunkPutter``) published
through the manager KV, the ``DataFeed``'s phase clock, and
``DataFeed.counters_snapshot()`` carrying both.  The rule under test
throughout: the instrumentation can drop its counters, never a chunk, a task
or a snapshot."""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from tensorflowonspark_tpu import (backend, cluster, manager, node, telemetry,
                                   util)
from tensorflowonspark_tpu.cluster import InputMode
from tensorflowonspark_tpu.datafeed import FEED_PHASES, DataFeed

FEEDER_US = ["feeder_{}_us".format(p) for p in node.FEEDER_PHASES]
FEED_US = ["feed_{}_us".format(p) for p in FEED_PHASES]


@pytest.fixture(autouse=True)
def _reset_global_tracer():
    yield
    telemetry.configure(False)


# ---------------------------------------------------------------------------
# a whole cluster: LocalBackend, SPARK mode, telemetry off
# ---------------------------------------------------------------------------

def _consume_and_report(args, ctx):
    """Consume the feed to its end; leave the feed's counters, the
    heartbeat's payload and what a hammering thread saw in files."""
    import json
    import threading

    from tensorflowonspark_tpu import node as node_mod

    feed = ctx.get_data_feed()
    seen = {"snapshots": 0, "errors": []}
    done = threading.Event()

    def hammer():
        while not done.is_set():
            try:
                feed.counters_snapshot()
                seen["snapshots"] += 1
            except Exception as e:
                seen["errors"].append(repr(e))
                return

    t = threading.Thread(target=hammer, daemon=True)
    t.start()
    rows = 0
    while not feed.should_stop():
        arrays, count = feed.next_batch_arrays(args["batch"])
        rows += count
    done.set()
    t.join(10)
    beat = node_mod._node_metrics_provider(ctx.mgr)()
    with open("report.json", "w") as f:
        json.dump({"rows": rows, "snapshot": feed.counters_snapshot(),
                   "heartbeat": beat, "hammer": seen}, f)


def test_feed_cycle_counters_through_a_cluster():
    rows, parts, epochs, calls = 48, 2, 2, 3
    data = [(np.full((4,), i, np.float32), i) for i in range(rows)]
    b = backend.LocalBackend(1)
    try:
        c = cluster.run(b, _consume_and_report, {"batch": 8},
                        num_executors=1, input_mode=InputMode.SPARK)
        t0 = time.monotonic()
        for _ in range(calls):
            c.train(backend.partition(data, parts), num_epochs=epochs,
                    chunk_size=4)
        wall_us = (time.monotonic() - t0) * 1e6
        # the executor shell ran every feed task: it never imported jax
        shell = b.map_partitions(
            [[0]], lambda it: [("jax" in sys.modules,
                                "jax.profiler" in sys.modules)])
        c.shutdown(grace_secs=1)
        with open(os.path.join(b.workdir_root, "executor-0",
                               "report.json")) as f:
            report = json.load(f)
    finally:
        b.stop()
    assert shell == [[(False, False)]]
    snap = report["snapshot"]
    assert report["rows"] == rows * epochs * calls
    assert snap["feed_items"] == rows * epochs * calls
    # telemetry is off: the feeder's counters are there all the same, and
    # the heartbeat still carries nothing
    assert report["heartbeat"] is None
    for key in FEEDER_US + FEED_US + ["feeder_items", "feeder_bytes",
                                      "feeder_tasks", "feeder_tasks_ahead",
                                      "feeder_tasks_ready"]:
        assert isinstance(snap[key], int) and snap[key] >= 0, key
    assert snap["feeder_items"] == rows * epochs * calls
    assert snap["feeder_tasks"] == parts * calls
    # the second partition of every call was sent ahead of the first's end;
    # the first of a call never is (the call before it had returned)
    assert snap["feeder_tasks_ahead"] == (parts - 1) * calls
    assert snap["feeder_tasks_ready"] <= snap["feeder_tasks_ahead"]
    # every task drains (it polls every 0.1 s) and publishes its whole
    # cycle at its end
    assert snap["feeder_drain_us"] >= parts * calls * 50000
    assert snap["feeder_pack_put_us"] > 0 and snap["feeder_replay_us"] > 0
    feeder_wall = sum(snap[k] for k in FEEDER_US)
    assert 0 < feeder_wall <= wall_us, (snap, wall_us)
    # the consumer: waited for every task, and the phases are its whole life
    assert snap["feed_wait_us"] > 0
    assert abs(snap["feed_wait_us"] / 1e6 - snap["feed_stall_secs"]) < 0.01
    assert report["hammer"]["errors"] == []
    assert report["hammer"]["snapshots"] > 0


def _consume_and_snapshot(args, ctx):
    import json

    feed = ctx.get_data_feed()
    while not feed.should_stop():
        feed.next_batch_arrays(args["batch"])
    with open("snapshot.json", "w") as f:
        json.dump(feed.counters_snapshot(), f)


@pytest.mark.parametrize("streaming, ahead", [(False, 1), (True, 0)])
def test_look_ahead_counters_of_a_two_partition_train(streaming, ahead):
    """A list of two partitions: the second is sent ahead (``feeder_tasks``
    2, ``feeder_tasks_ahead`` 1).  The same two from an iterator are two
    jobs of one partition: nothing to send ahead.  The four phases still sum
    to the clock's wall time: no more than this test's own."""
    data = [(np.full((4,), i, np.float32), i) for i in range(32)]
    parts = backend.partition(data, 2)
    b = backend.LocalBackend(1)
    try:
        c = cluster.run(b, _consume_and_snapshot, {"batch": 8},
                        num_executors=1, input_mode=InputMode.SPARK)
        t0 = time.monotonic()
        c.train(iter(parts) if streaming else parts, chunk_size=4)
        wall_us = (time.monotonic() - t0) * 1e6
        c.shutdown(grace_secs=1)
        with open(os.path.join(b.workdir_root, "executor-0",
                               "snapshot.json")) as f:
            snap = json.load(f)
    finally:
        b.stop()
    assert snap["feed_items"] == snap["feeder_items"] == 32
    assert snap["feeder_tasks"] == 2
    assert snap["feeder_tasks_ahead"] == ahead
    assert 0 <= snap["feeder_tasks_ready"] <= ahead
    # published at each task's end: the gap before the first task (the
    # process's life until then) is in it, the time after the last is not
    assert all(snap[k] >= 0 for k in FEEDER_US)
    in_tasks = sum(snap[k] for k in FEEDER_US) - snap["feeder_between_tasks_us"]
    assert 0 < in_tasks <= wall_us, (snap, wall_us)


HANDOVER = ["feeder_handover_oob_bytes", "feeder_handover_inband_bytes",
            "feeder_handover_us"]


@pytest.mark.parametrize("side", [128, 16])
def test_handover_counters_of_a_two_partition_train(side):
    """``LocalBackend`` says how each feed task's partition came in, and
    the feeder publishes it once a task with its other counters.  Rows of
    64 KB (``side`` 128) travel beside the pipe in a shared-memory segment,
    rows of 1 KB cross it in band; either way the counters of a
    two-partition ``train`` are the two tasks' added up."""
    rows, parts = 16, 2
    data = [(np.full((side, side), i, np.float32), i) for i in range(rows)]
    row_bytes = side * side * 4
    b = backend.LocalBackend(1)
    try:
        c = cluster.run(b, _consume_and_snapshot, {"batch": 8},
                        num_executors=1, input_mode=InputMode.SPARK)
        t0 = time.monotonic()
        c.train(backend.partition(data, parts), chunk_size=4)
        wall_us = (time.monotonic() - t0) * 1e6
        c.shutdown(grace_secs=1)
        with open(os.path.join(b.workdir_root, "executor-0",
                               "snapshot.json")) as f:
            snap = json.load(f)
    finally:
        b.stop()
    assert snap["feed_items"] == snap["feeder_items"] == rows
    assert snap["feeder_tasks"] == parts
    for key in HANDOVER:
        assert isinstance(snap[key], int), key
    if row_bytes >= backend._BESIDE_MIN:
        assert snap["feeder_handover_oob_bytes"] == rows * row_bytes
        # the task's closure, the labels, the arrays' headers: twice
        assert 2 * 2000 < snap["feeder_handover_inband_bytes"] < 2 * 20000
    else:
        assert snap["feeder_handover_oob_bytes"] == 0
        assert (rows * row_bytes + 2 * 2000
                < snap["feeder_handover_inband_bytes"]
                < rows * row_bytes + 2 * 20000)
    # two hand-overs, each shorter than the whole call
    assert 0 < snap["feeder_handover_us"] < 2 * wall_us


def test_handover_counters_are_zero_outside_a_local_backend(harness):
    """A feed task that no ``LocalBackend`` executor runs (Spark's Python
    worker; here, this process) publishes the three hand-over counters as
    zeros, with the others: the hand-over was somebody else's."""
    feed = DataFeed(harness.mgr)
    harness.consume(feed)
    fn = node.train(harness.cluster_info, harness.meta, chunk_size=4)
    big = [(np.full((128, 128), i, np.float32), i) for i in range(8)]
    assert fn(iter(big)) == [8]
    published = harness.mgr.get("feeder_metrics")
    assert published["feeder_tasks"] == 1 and published["feeder_items"] == 8
    assert [published[key] for key in HANDOVER] == [0, 0, 0]
    assert tuple(backend.task_handover()) == (False, False, 0, 0, 0)
    snap = feed.counters_snapshot()
    assert [snap[key] for key in HANDOVER] == [0, 0, 0]
    harness.finish(feed)
    assert harness.errors == []


# ---------------------------------------------------------------------------
# the feeder in this process: the identity against its own clock
# ---------------------------------------------------------------------------

class _Harness(object):
    """A manager, the files and the cluster description that
    ``node.train``'s task closure needs to run in the test's process, and a
    consumer thread over a ``DataFeed``."""

    def __init__(self, tmp_path, monkeypatch, qname="input"):
        monkeypatch.chdir(tmp_path)
        util.write_executor_id(0)
        self.mgr = manager.start(b"feed-cycle", [qname, "error"])
        self.mgr.set("state", "running")
        self.cluster_info = [{
            "host": util.get_ip_address(), "executor_id": 0,
            "job_name": "worker", "task_index": 0,
            "addr": self.mgr.address, "authkey": self.mgr.authkey.hex()}]
        self.meta = {"id": "feed-cycle-test", "server_addr": None}
        self.qname = qname
        self.rows = []
        self.errors = []
        self._stop = threading.Event()

    def consume(self, feed, batch=8):
        def loop():
            try:
                while not self._stop.is_set() and not feed.should_stop():
                    arrays, count = feed.next_batch_arrays(batch)
                    if count:
                        self.rows.extend(np.asarray(arrays[1]).tolist())
            except Exception as e:
                self.errors.append(repr(e))

        self.thread = threading.Thread(target=loop, daemon=True)
        self.thread.start()

    def finish(self, feed):
        self._stop.set()
        feed.interrupt()
        self.thread.join(10)
        assert not self.thread.is_alive()
        self.mgr.shutdown()


@pytest.fixture
def harness(tmp_path, monkeypatch):
    h = _Harness(tmp_path, monkeypatch)
    yield h
    try:
        h.mgr.shutdown()
    except Exception:
        pass


def _data(n):
    return [(np.full((4,), i, np.float32), i) for i in range(n)]


def test_feeder_phases_sum_to_the_feeders_wall_time(harness):
    feed = DataFeed(harness.mgr)
    harness.consume(feed)
    t0 = time.monotonic_ns()
    clock = node._feeder_clocks[harness.qname] = telemetry.PhaseClock(
        node.FEEDER_PHASES)
    fn = node.train(harness.cluster_info, harness.meta, chunk_size=4,
                    num_epochs=2)
    for _ in range(3):
        assert fn(iter(_data(24))) == [48]
        time.sleep(0.02)        # between two tasks
    published = harness.mgr.get("feeder_metrics")
    rest = clock.delta("feeder_")   # the gap after the last task
    wall_us = (time.monotonic_ns() - t0) / 1e3
    total = {k: published[k] + rest[k] for k in FEEDER_US}
    assert abs(sum(total.values()) - wall_us) < 1000, (total, wall_us)
    assert published["feeder_items"] == 3 * 48
    assert published["feeder_tasks"] == 3
    # no LocalBackend executor runs these tasks: nothing came in ahead
    assert published["feeder_tasks_ahead"] == 0
    assert published["feeder_tasks_ready"] == 0
    assert total["feeder_between_tasks_us"] >= 3 * 20000
    assert total["feeder_drain_us"] > 0
    for key in ("feeder_source_us", "feeder_pack_put_us", "feeder_replay_us"):
        assert total[key] > 0, key
    snap = feed.counters_snapshot()
    for key in FEEDER_US + FEED_US + ["feeder_tasks_ahead",
                                      "feeder_tasks_ready"]:
        assert key in snap, key
    harness.finish(feed)
    assert harness.errors == []
    assert sorted(harness.rows) == sorted(list(range(24)) * 6)
    # the consumer's phases are the DataFeed's whole life so far
    snap = feed.counters_snapshot()
    assert all(snap[k] >= 0 for k in FEED_US)
    assert snap["feed_wait_us"] > 0 and snap["feed_read_us"] > 0
    assert snap["feed_assemble_us"] > 0


def test_consumer_phases_sum_to_its_wall_time_with_one_copy_a_row(harness):
    """ISSUE 33: the ring read with its one copy (and the ack) is ``read``,
    what is left of building a batch ``assemble``; both stay in the
    snapshot, beside how often a batch found a buffer waiting."""
    t0 = time.monotonic_ns()
    feed = DataFeed(harness.mgr)
    made_us = (time.monotonic_ns() - t0) / 1e3   # its clock started in here
    handed = []

    def loop():     # chunks of 5 against batches of 8: most chunks straddle
        while not feed.should_stop():
            arrays, count = feed.next_batch_arrays(8)
            if count:
                harness.rows.extend(arrays[1].tolist())
                handed.append(feed.release(arrays))

    t = threading.Thread(target=loop, daemon=True)
    t.start()
    node._feeder_clocks[harness.qname] = telemetry.PhaseClock(
        node.FEEDER_PHASES)
    fn = node.train(harness.cluster_info, harness.meta, chunk_size=5,
                    num_epochs=2)
    assert fn(iter(_data(40))) == [80]
    harness.mgr.get_queue(harness.qname).put(None)
    t.join(10)
    assert not t.is_alive()
    before_us = (time.monotonic_ns() - t0) / 1e3
    snap = feed.counters_snapshot()     # reads the clock, then the manager
    after_us = (time.monotonic_ns() - t0) / 1e3
    assert harness.rows == list(range(40)) * 2
    for key in FEED_US + ["feed_batch_buffers_reused",
                          "feed_batch_buffers_new"]:
        assert isinstance(snap[key], int) and snap[key] >= 0, key
    # the phases are the feed's whole life (each rounds down to a us)
    assert before_us - made_us - 10 <= sum(snap[k] for k in FEED_US) \
        <= after_us, (snap, made_us, before_us, after_us)
    assert snap["feed_read_us"] > 0 and snap["feed_wait_us"] > 0
    assert snap.get("wire_colv1", 0) + snap.get("wire_queue", 0) == 16
    # ten whole batches, each handed back: the first is new memory, the
    # rest is that memory again
    assert handed == [True] * 10
    assert snap["feed_batch_buffers_new"] == 1
    assert snap["feed_batch_buffers_reused"] == 9


def test_inference_feeder_accounts_on_its_own_queue(harness):
    """The inference closure shares ``_ChunkPutter``: its task is accounted
    the same way and leaves the clock between tasks."""
    clock = node._feeder_clocks[harness.qname] = telemetry.PhaseClock(
        node.FEEDER_PHASES)
    putter = node._ChunkPutter(harness.mgr.get_queue(harness.qname),
                               harness.meta, 0, harness.qname, 5)
    assert putter.clock is clock
    assert node._feed_blocks(iter(_data(10)), putter, 4) == 10
    snap = clock.snapshot("feeder_")
    assert snap["feeder_pack_put_us"] > 0 and snap["feeder_source_us"] > 0
    assert snap["feeder_replay_us"] == snap["feeder_drain_us"] == 0


class _NoMetricsManager(object):
    """A manager whose KV refuses the metrics key (and nothing else)."""

    def __init__(self, mgr):
        self._mgr = mgr
        self.refused = 0

    def get(self, key):
        if key == "feeder_metrics":
            self.refused += 1
            raise EOFError("manager gone, for metrics only")
        return self._mgr.get(key)

    def set(self, key, value):
        if key == "feeder_metrics":
            self.refused += 1
            raise EOFError("manager gone, for metrics only")
        return self._mgr.set(key, value)

    def get_queue(self, qname):
        return self._mgr.get_queue(qname)


def test_a_manager_that_refuses_metrics_costs_only_the_metrics(
        harness, monkeypatch):
    flaky = _NoMetricsManager(harness.mgr)
    monkeypatch.setattr(node, "_get_manager", lambda *a: flaky)
    feed = DataFeed(flaky)
    harness.consume(feed)
    fn = node.train(harness.cluster_info, harness.meta, chunk_size=4,
                    num_epochs=2)
    assert fn(iter(_data(24))) == [48]      # the task did not fail
    assert flaky.refused >= 1               # the publication was refused
    assert harness.mgr.get("feeder_metrics") is None
    snap = feed.counters_snapshot()         # and the snapshot still returns
    assert snap["feed_items"] == 48
    assert all(k in snap for k in FEED_US)
    assert not any(k.startswith("feeder_") for k in snap)
    harness.finish(feed)
    assert harness.errors == []
    assert sorted(harness.rows) == sorted(list(range(24)) * 2)


def test_published_junk_never_reaches_a_snapshot(harness):
    """Whatever sits under the KV's key, a snapshot holds numbers only: the
    benchmark subtracts two snapshots key by key."""
    feed = DataFeed(harness.mgr)
    for junk in (None, "text", 7, ["a"],
                 {"feeder_items": 5, "feeder_note": "x", "feeder_flag": True,
                  "feeder_none": None, "feeder_pack_put_us": 2.5}):
        harness.mgr.set("feeder_metrics", junk)
        snap = feed.counters_snapshot()
        assert all(isinstance(v, (int, float)) and not isinstance(v, bool)
                   for v in snap.values()), (junk, snap)
    assert snap["feeder_items"] == 5 and snap["feeder_pack_put_us"] == 2.5
    assert "feeder_note" not in snap and "feeder_flag" not in snap


class _CountingManager(object):
    """A manager that counts the reads of the feeders' KV."""

    def __init__(self, mgr):
        self._mgr = mgr
        self.metric_gets = 0

    def get(self, key):
        self.metric_gets += key == "feeder_metrics"
        return self._mgr.get(key)

    def get_queue(self, qname):
        return self._mgr.get_queue(qname)


def test_heartbeat_reads_the_feeders_part_once(harness, tmp_path):
    """A DataFeed's public snapshot carries the KV's ``feeder_*``; the
    heartbeat provider takes the feeds' own counters and reads the KV
    itself: with two feeds on one node a beat costs one round trip for it
    and the payload holds each feeder counter once."""
    telemetry.configure(True, str(tmp_path / "telemetry"))
    harness.mgr.set("feeder_metrics", {"feeder_items": 10,
                                       "feeder_drain_us": 7})
    mgr = _CountingManager(harness.mgr)
    feeds = [DataFeed(mgr), DataFeed(mgr)]
    saved = list(node._feeds)
    try:
        del node._feeds[:]
        for feed in feeds:
            node._register_feed(feed)
        beat = node._node_metrics_provider(mgr, harness.qname)()
    finally:
        node._feeds[:] = saved
    assert mgr.metric_gets == 1
    assert beat["feeder_items"] == 10 and beat["feeder_drain_us"] == 7
    assert "feed_wait_us" in beat and "feed_away_us" in beat
    assert feeds[0].counters_snapshot()["feeder_items"] == 10
    assert mgr.metric_gets == 2


def test_snapshots_from_another_thread_during_a_whole_training_feed(harness):
    """``counters_snapshot()`` of the DataFeed, the ShardedFeed and the
    Trainer, hammered from a second thread while ``fit_feed`` runs."""
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu.parallel import build_mesh
    from tensorflowonspark_tpu.parallel.infeed import ShardedFeed
    from tensorflowonspark_tpu.train import Trainer

    feed = DataFeed(harness.mgr, input_mapping={"a_x": "x", "b_y": "y"})
    mesh = build_mesh()
    sharded = ShardedFeed(feed, mesh, global_batch_size=8, prefetch=2)

    def loss(params, batch, mask):
        pred = jnp.asarray(batch["x"]) @ params["w"]
        err = (pred - jnp.asarray(batch["y"], jnp.float32)) ** 2 * mask
        return err.sum() / jnp.maximum(mask.sum(), 1.0), {}

    trainer = Trainer(loss, {"w": jnp.zeros((4,))}, optax.sgd(0.01),
                      mesh=mesh, batch_size=8, log_steps=4)
    seen = {"n": 0, "errors": [], "keys": set()}
    done = threading.Event()

    def hammer():
        while not done.is_set():
            try:
                for source in (feed, sharded, trainer):
                    seen["keys"].update(source.counters_snapshot())
                seen["n"] += 1
            except Exception as e:
                seen["errors"].append(repr(e))
                return

    t = threading.Thread(target=hammer, daemon=True)
    t.start()

    def feeder():
        fn = node.train(harness.cluster_info, harness.meta, chunk_size=4,
                        num_epochs=2)
        for _ in range(3):
            fn(iter(_data(32)))
        harness.mgr.get_queue(harness.qname).put(None)

    f = threading.Thread(target=feeder, daemon=True)
    f.start()
    stats = trainer.fit_feed(sharded)
    f.join(30)
    done.set()
    t.join(10)
    assert not f.is_alive() and not t.is_alive()
    assert stats["global_steps"] == 3 * 64 // 8
    assert seen["errors"] == [] and seen["n"] > 0
    assert {"feed_wait_us", "feeder_drain_us", "infeed_put_us",
            "dispatch_gap_us"} <= seen["keys"]


def test_program_spans_reach_the_tracer_under_their_names(harness, tmp_path):
    """Telemetry on: the feeder's task spans (one of each a task, none a
    chunk), the consumer's, the infeed's and the step loop's, each under
    the name docs/OBSERVABILITY.md gives it."""
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu.parallel import build_mesh
    from tensorflowonspark_tpu.parallel.infeed import ShardedFeed
    from tensorflowonspark_tpu.train import Trainer

    tracer = telemetry.configure(True, str(tmp_path / "telemetry"))
    harness.meta["telemetry"] = telemetry.meta_spec(
        True, str(tmp_path / "telemetry"))
    feed = DataFeed(harness.mgr, input_mapping={"a_x": "x", "b_y": "y"})
    mesh = build_mesh()
    sharded = ShardedFeed(feed, mesh, global_batch_size=8, prefetch=1,
                          transform=lambda arrays: arrays)

    def loss(params, batch, mask):
        pred = jnp.asarray(batch["x"]) @ params["w"]
        err = (pred - jnp.asarray(batch["y"], jnp.float32)) ** 2 * mask
        return err.sum() / jnp.maximum(mask.sum(), 1.0), {}

    trainer = Trainer(loss, {"w": jnp.zeros((4,))}, optax.sgd(0.01),
                      mesh=mesh, batch_size=8, log_steps=4)

    def feeder():
        fn = node.train(harness.cluster_info, harness.meta, chunk_size=4,
                        num_epochs=2)
        for _ in range(2):
            fn(iter(_data(32)))
        harness.mgr.get_queue(harness.qname).put(None)

    f = threading.Thread(target=feeder, daemon=True)
    f.start()
    trainer.fit_feed(sharded, on_steps=lambda n: time.sleep(0.02))
    f.join(30)
    assert not f.is_alive()
    counts = {}
    for event in list(tracer._events):
        if event["ph"] == "X":
            counts[event["name"]] = counts.get(event["name"], 0) + 1
    for name in ("feed/partition", "feed/first_pass", "feed/replay",
                 "feed/drain"):
        assert counts[name] == 2, (name, counts)
    assert counts["train/dispatch"] == counts["train/on_steps"] == 16
    assert counts["train/next_batch"] == 17     # the last one finds the end
    assert counts["infeed/assemble"] >= 16
    assert counts["infeed/transform"] == counts["infeed/device_put"] == 16
    # once a chunk: for the profiler only, the tracer's buffer is bounded
    assert "feed/wait" not in counts and "feed/read" not in counts
    # the hook sleeps, the prefetch queue holds one batch: the infeed waited
    assert counts.get("infeed/queue_full", 0) > 0
