"""Distributed integration tests over LocalBackend (reference
``test/test_TFCluster.py``): real multi-process executors, no mocks."""

import os

import pytest

from tensorflowonspark_tpu import backend, cluster, shmring
from tensorflowonspark_tpu.cluster import InputMode


@pytest.fixture
def local_backend():
    b = backend.LocalBackend(2)
    yield b
    b.stop()


def test_basic_independent_nodes(local_backend):
    """Run independent single-node fns on all executors (reference
    ``test_TFCluster.py:16-27``)."""

    def map_fun(args, ctx):
        # a trivially verifiable computation, persisted per-node
        with open("result.txt", "w") as f:
            f.write("{}:{}:{}".format(ctx.job_name, ctx.task_index, 3 * 7))

    c = cluster.run(local_backend, map_fun, tf_args=[], num_executors=2,
                    input_mode=InputMode.FILES)
    assert len(c.cluster_info) == 2
    assert {n["job_name"] for n in c.cluster_info} == {"worker"}
    c.shutdown()
    # verify both nodes ran
    found = []
    for i in range(2):
        path = os.path.join(local_backend.workdir_root,
                            "executor-{}".format(i), "result.txt")
        with open(path) as f:
            found.append(f.read())
    assert sorted(found) == ["worker:0:21", "worker:1:21"]


def test_inputmode_spark_train_and_inference(local_backend):
    """Full feed → compute → result round trip (reference
    ``test_TFCluster.py:29-48``)."""

    def map_fun(args, ctx):
        feed = ctx.get_data_feed(train_mode=False)
        while not feed.should_stop():
            batch = feed.next_batch(3)
            if batch:
                feed.batch_results([x * x for x in batch])

    c = cluster.run(local_backend, map_fun, tf_args=[], num_executors=2,
                    input_mode=InputMode.SPARK)
    data = backend.partition(range(10), 4)
    results = c.inference(data)
    assert sorted(results) == sorted(x * x for x in range(10))
    c.shutdown()


def test_train_feed_consumed(local_backend):
    def map_fun(args, ctx):
        feed = ctx.get_data_feed()
        total = 0
        while not feed.should_stop():
            for x in feed.next_batch(5):
                total += x
        with open("sum.txt", "w") as f:
            f.write(str(total))

    c = cluster.run(local_backend, map_fun, tf_args=[], num_executors=2,
                    input_mode=InputMode.SPARK)
    c.train(backend.partition(range(20), 4), num_epochs=2)
    c.shutdown()
    totals = 0
    for i in range(2):
        with open(os.path.join(local_backend.workdir_root,
                               "executor-{}".format(i), "sum.txt")) as f:
            totals += int(f.read())
    assert totals == sum(range(20)) * 2


def test_failure_during_feeding(local_backend):
    """Exception in user code during feeding propagates via the error queue
    with a short feed_timeout (reference ``test_TFCluster.py:50-68``)."""

    def map_fun(args, ctx):
        from tensorflowonspark_tpu import fault

        feed = ctx.get_data_feed()
        feed.next_batch(1)
        fault.fail("injected mid-feed failure")

    c = cluster.run(local_backend, map_fun, tf_args=[], num_executors=2,
                    input_mode=InputMode.SPARK)
    with pytest.raises(RuntimeError, match="injected mid-feed failure"):
        c.train(backend.partition(range(100), 2), feed_timeout=10)
    with pytest.raises(SystemExit):
        c.shutdown()


def test_failure_after_feeding(local_backend):
    """Exception raised after all data was consumed is caught by
    ``shutdown(grace_secs)``'s late-error check (reference
    ``test_TFCluster.py:70-91``)."""

    def map_fun(args, ctx):
        from tensorflowonspark_tpu import fault

        feed = ctx.get_data_feed()
        while not feed.should_stop():
            feed.next_batch(5)
        fault.fail("injected post-feed failure")

    c = cluster.run(local_backend, map_fun, tf_args=[], num_executors=2,
                    input_mode=InputMode.SPARK)
    c.train(backend.partition(range(10), 2))
    with pytest.raises(SystemExit):
        c.shutdown(grace_secs=3)


def test_master_node_and_roles(local_backend):
    def map_fun(args, ctx):
        with open("role.txt", "w") as f:
            f.write("{}:{}:pid{}".format(ctx.job_name, ctx.task_index,
                                         ctx.process_id))

    c = cluster.run(local_backend, map_fun, tf_args=[], num_executors=2,
                    master_node="chief", input_mode=InputMode.FILES)
    jobs = {(n["job_name"], n["task_index"]) for n in c.cluster_info}
    assert jobs == {("chief", 0), ("worker", 0)}
    # chief is always jax process 0 (stable coordinator assignment)
    assert c.cluster_info[0]["job_name"] == "chief"
    c.shutdown()


def test_executor_env_reaches_nodes(local_backend):
    """TPU/XLA perf knobs (device_info.tpu_env) must land in every node's
    process env before user code runs (reference GPU-thread tuning analog,
    ``common.py:143-166``)."""
    from tensorflowonspark_tpu import device_info

    env = device_info.tpu_env(
        libtpu_init_args=["--xla_tpu_enable_data_parallel_all_reduce_opt=true"],
        xla_flags=["--xla_dump_disable_metadata"],
        TFOS_TEST_KNOB="42")
    assert env["LIBTPU_INIT_ARGS"] == \
        "--xla_tpu_enable_data_parallel_all_reduce_opt=true"
    assert "--xla_dump_disable_metadata" in env["XLA_FLAGS"]

    def map_fun(args, ctx):
        with open("env.txt", "w") as f:
            f.write("{}|{}".format(os.environ.get("LIBTPU_INIT_ARGS", ""),
                                   os.environ.get("TFOS_TEST_KNOB", "")))

    c = cluster.run(local_backend, map_fun, tf_args=[], num_executors=2,
                    input_mode=InputMode.FILES, executor_env=env)
    c.shutdown()
    for i in range(2):
        path = os.path.join(local_backend.workdir_root,
                            "executor-{}".format(i), "env.txt")
        with open(path) as f:
            libtpu, knob = f.read().split("|")
        assert "--xla_tpu_enable_data_parallel_all_reduce_opt=true" in libtpu
        assert knob == "42"


def test_tensorboard_lifecycle(local_backend, tmp_path, monkeypatch):
    """Framework-managed TensorBoard: launched on the first worker, port in
    the roster, URL exposed, killed at shutdown (reference
    ``TFSparkNode.py:199-225,522-528`` — untested there; tested here)."""
    import stat
    import time

    # stub `tensorboard` on PATH: a script that parks until killed
    stub = tmp_path / "tensorboard"
    stub.write_text("import time\ntime.sleep(600)\n")
    stub.chmod(stub.stat().st_mode | stat.S_IEXEC)
    monkeypatch.setenv("PATH", str(tmp_path) + os.pathsep + os.environ["PATH"])

    def map_fun(args, ctx):
        pass

    c = cluster.run(local_backend, map_fun, tf_args=[], num_executors=2,
                    input_mode=InputMode.FILES, tensorboard=True,
                    log_dir=str(tmp_path / "tb_logs"),
                    executor_env={"PATH": str(tmp_path) + os.pathsep
                                  + os.environ["PATH"]})
    tb_nodes = [n for n in c.cluster_info if n.get("tb_pid")]
    assert len(tb_nodes) == 1, c.cluster_info
    node_meta = tb_nodes[0]
    assert node_meta["tb_port"] > 0
    assert c.tensorboard_url() == "http://{}:{}".format(
        node_meta["host"], node_meta["tb_port"])
    pid = node_meta["tb_pid"]
    os.kill(pid, 0)  # alive while the cluster runs

    c.shutdown()
    # dead (or zombie awaiting reap) after shutdown's kill
    deadline = time.time() + 15
    while time.time() < deadline:
        try:
            with open("/proc/{}/stat".format(pid)) as f:
                state = f.read().split(")")[-1].split()[0]
            if state == "Z":
                break
        except OSError:
            break
        time.sleep(0.2)
    else:
        raise AssertionError("tensorboard stub pid {} still alive".format(pid))


def test_columnar_feed_epochs_and_chunk_size(local_backend):
    """Columnar end to end through the cluster: ndarray-tuple rows arrive as
    ColChunk blocks, the worker consumes them with next_batch_arrays, epochs
    replay executor-side, and chunk_size is plumbed from cluster.train."""
    import numpy as np

    def map_fun(args, ctx):
        feed = ctx.get_data_feed()
        total_rows = 0
        label_sum = 0
        while not feed.should_stop():
            arrays, count = feed.next_batch_arrays(6)
            if count:
                x, y = arrays
                assert x.shape[1:] == (4,), x.shape
                total_rows += count
                label_sum += int(y.sum())
        with open("colstats.txt", "w") as f:
            f.write("{}:{}".format(total_rows, label_sum))

    rows = [(np.full(4, i, np.float32), i) for i in range(20)]
    c = cluster.run(local_backend, map_fun, tf_args=[], num_executors=2,
                    input_mode=InputMode.SPARK)
    c.train(backend.partition(rows, 4), num_epochs=3, chunk_size=4)
    c.shutdown()
    rows_seen = labels = 0
    for i in range(2):
        with open(os.path.join(local_backend.workdir_root,
                               "executor-{}".format(i), "colstats.txt")) as f:
            r, s = f.read().split(":")
            rows_seen += int(r)
            labels += int(s)
    assert rows_seen == 20 * 3
    assert labels == sum(range(20)) * 3


def test_evaluator_role_own_world(tmp_path):
    """eval_node parity (reference mnist_tf.py:109-115 train_and_evaluate):
    the evaluator is NOT part of the workers' jax.distributed world (its own
    single-process world reads checkpoints), workers' num_processes excludes
    it, and shutdown signals it via its control queue like a ps node."""
    import argparse
    import json
    import time

    shared = str(tmp_path / "shared")
    os.makedirs(shared, exist_ok=True)

    def map_fun(args, ctx):
        import jax

        if ctx.job_name == "evaluator":
            # own world: no slot in the workers' jax.distributed job set
            assert ctx.process_id is None, ctx.process_id
            ckpt = os.path.join(args.shared, "ckpt.json")
            deadline = time.time() + 60
            while not os.path.exists(ckpt) and time.time() < deadline:
                time.sleep(0.2)
            with open(ckpt) as f:
                w = json.load(f)["w"]
            # evaluate on this node's own single-process jax world
            result = float(jax.jit(lambda x: x * 2)(w))
            with open(os.path.join(args.shared, "eval.json"), "w") as f:
                json.dump({"eval": result}, f)
            return
        # workers: the shared world has exactly the two worker slots
        assert ctx.num_processes == 2, ctx.num_processes
        assert ctx.process_id in (0, 1)
        if ctx.is_chief():
            with open(os.path.join(args.shared, "ckpt.json"), "w") as f:
                json.dump({"w": 21}, f)

    b = backend.LocalBackend(3)
    try:
        args = argparse.Namespace(shared=shared)
        c = cluster.run(b, map_fun, args, num_executors=3, eval_node=True,
                        input_mode=InputMode.FILES)
        assert {n["job_name"] for n in c.cluster_info} == {"worker", "evaluator"}
        c.shutdown(grace_secs=1)
    finally:
        b.stop()
    deadline = time.time() + 30
    eval_path = os.path.join(shared, "eval.json")
    while not os.path.exists(eval_path) and time.time() < deadline:
        time.sleep(0.2)
    with open(eval_path) as f:
        assert json.load(f)["eval"] == 42.0


def test_driver_ps_nodes(local_backend):
    """driver_ps_nodes parity (reference TFCluster.py:291-309): ps roles run
    in driver daemon threads, so a 2-executor backend hosts a 3-node cluster
    (1 ps + 2 workers) with every executor slot spent on a worker."""

    def map_fun(args, ctx):
        if ctx.job_name == "ps":
            return  # parked by the node runtime until shutdown
        feed = ctx.get_data_feed(train_mode=False)
        while not feed.should_stop():
            batch = feed.next_batch(3)
            if batch:
                feed.batch_results([x + 100 for x in batch])

    c = cluster.run(local_backend, map_fun, tf_args=[], num_executors=3,
                    num_ps=1, driver_ps_nodes=True,
                    input_mode=InputMode.SPARK)
    ps = [n for n in c.cluster_info if n["job_name"] == "ps"]
    workers = [n for n in c.cluster_info if n["job_name"] == "worker"]
    assert len(ps) == 1 and len(workers) == 2
    assert ps[0]["pid"] == os.getpid()          # ps lives on the driver
    assert all(n["pid"] != os.getpid() for n in workers)
    results = c.inference(backend.partition(range(12), 4))
    assert sorted(results) == [x + 100 for x in range(12)]
    c.shutdown(grace_secs=1)


def test_columnar_feed_without_shm_ring():
    """TFOS_DISABLE_SHM: columnar chunks travel in-queue (no ring), same
    semantics — the fallback path for hosts without the native transport."""
    import numpy as np

    def map_fun(args, ctx):
        feed = ctx.get_data_feed()
        total = 0
        while not feed.should_stop():
            arrays, count = feed.next_batch_arrays(8)
            if count:
                total += int(arrays[1].sum())
        with open("sum.txt", "w") as f:
            f.write(str(total))

    b = backend.LocalBackend(2, env={"TFOS_DISABLE_SHM": "1"})
    try:
        rows = [(np.full(3, i, np.float32), i) for i in range(16)]
        c = cluster.run(b, map_fun, tf_args=[], num_executors=2,
                        input_mode=InputMode.SPARK)
        c.train(backend.partition(rows, 4), num_epochs=2, chunk_size=4)
        c.shutdown()
        total = 0
        for i in range(2):
            with open(os.path.join(b.workdir_root,
                                   "executor-{}".format(i), "sum.txt")) as f:
                total += int(f.read())
        assert total == sum(range(16)) * 2
    finally:
        b.stop()


def test_hard_killed_consumer_surfaces_feed_timeout(local_backend, tmp_path):
    """SIGKILL the training process mid-run (the OOM-killer scenario): it
    can't push an error through the queue, so the feeder must surface the
    failure to the driver instead of hanging — via the node_pid fast-fail
    when it catches the death, else the feed_timeout watchdog (reference
    feed_timeout, TFSparkNode.py:410-418)."""
    import signal
    import time as _time

    pid_dir = str(tmp_path / "pids")
    os.makedirs(pid_dir)

    def map_fun(args, ctx):
        import os as _os
        import time as _t

        # write-then-rename: the driver polls listdir and must never read
        # a created-but-unflushed file
        tmp = os.path.join(args, ".tmp-%d" % ctx.process_id)
        with open(tmp, "w") as f:
            f.write(str(_os.getpid()))
        _os.rename(tmp, os.path.join(args, "pid-%d" % ctx.process_id))
        feed = ctx.get_data_feed()
        feed.next_batch(1)
        _t.sleep(600)  # hold the queue un-drained until killed

    c = cluster.run(local_backend, map_fun, tf_args=pid_dir,
                    num_executors=2, input_mode=InputMode.SPARK)
    deadline = _time.time() + 30
    while len([n for n in os.listdir(pid_dir) if n.startswith("pid-")]) < 2:
        assert _time.time() < deadline, "consumers never reported pids"
        _time.sleep(0.2)
    for name in os.listdir(pid_dir):
        if name.startswith("pid-"):
            with open(os.path.join(pid_dir, name)) as f:
                os.kill(int(f.read()), signal.SIGKILL)

    with pytest.raises(Exception, match="node process .* died|Timeout"):
        c.train(backend.partition(range(100), 2), feed_timeout=8)
    with pytest.raises(SystemExit):
        c.shutdown(grace_secs=1)


class _ShutdownFakes:
    """Minimal backend/server/job doubles for driving TPUCluster.shutdown
    coverage logic without a live cluster."""

    class Backend:
        def __init__(self, reached):
            self.reached = reached  # executor ids the poison tasks "reach"
            self.stopped = False

        def map_partitions(self, parts, fn, timeout=None):
            return [[i] if i in self.reached else [] for (i,) in parts]

        def stop(self):
            self.stopped = True

    class Server:
        done = False

        def stop(self):
            pass

    class Job:
        error = None

        def done(self):
            return True

        def wait(self, timeout=None):
            pass


def _mk_cluster(reached, worker_states):
    """Cluster of 2 workers; poison tasks reach `reached`; each worker id
    maps to a live manager seeded with worker_states[id] (or no manager at
    all for state None — a vanished executor)."""
    from tensorflowonspark_tpu import manager as mgr_mod

    info, handles = [], []
    for i, state in worker_states.items():
        authkey = b"shutdown-test-%d" % i
        addr = None
        if state is not None:
            h = mgr_mod.start(authkey, ["control"])
            h.set("state", state)
            handles.append(h)
            addr = h.address
        # host = the driver's own IP: this scenario is genuinely same-host
        # (LocalBackend), which is what makes a failed unix-socket probe
        # authoritative evidence of a dead executor
        from tensorflowonspark_tpu import util as util_mod

        info.append({"executor_id": i, "job_name": "worker", "task_index": i,
                     "host": util_mod.get_ip_address(),
                     "addr": addr or "/tmp/gone-%d" % i,
                     "authkey": authkey.hex()})
    c = cluster.TPUCluster(
        _ShutdownFakes.Backend(reached), {"id": "t", "spark_mode": False},
        info, cluster.InputMode.SPARK, _ShutdownFakes.Server(),
        _ShutdownFakes.Job(), {}, ["input", "output"])
    return c, handles


def test_shutdown_unconfirmed_but_finished_is_clean():
    """Poison tasks never reach node 1, but its manager reports finished:
    shutdown must complete with exit 0 (no SystemExit)."""
    c, handles = _mk_cluster(reached={0},
                             worker_states={0: "running", 1: "finished"})
    try:
        c.shutdown(grace_secs=1, timeout=60)  # must not raise
    finally:
        for h in handles:
            h.shutdown()


def test_shutdown_vanished_executor_exits_nonzero():
    """A worker that never confirms poisoning AND has no reachable manager
    (executor died) must fail the driver with exit status 1 (reference
    TFCluster.py:177-181), not a warning + exit 0."""
    c, handles = _mk_cluster(reached={0},
                             worker_states={0: "running", 1: None})
    try:
        with pytest.raises(SystemExit) as exc:
            c.shutdown(grace_secs=1, timeout=60)
        assert exc.value.code == 1
        assert "never confirmed" in c.tf_status["error"]
    finally:
        for h in handles:
            h.shutdown()


def test_shutdown_live_running_node_is_unresponsive_not_dead():
    """A worker whose manager probe SUCCEEDS and reports 'running' is alive
    — the poison markers just never landed on it.  That must be a warning
    (shutdown-coverage gap), not the fatal 'executor died' latch."""
    c, handles = _mk_cluster(reached={0},
                             worker_states={0: "running", 1: "running"})
    try:
        c.shutdown(grace_secs=1, timeout=60)  # must not raise
        assert "error" not in c.tf_status
    finally:
        for h in handles:
            h.shutdown()


def test_shutdown_remote_unreachable_is_warning_not_fatal():
    """From a REMOTE driver, a worker's unix-socket manager is unreachable
    by design (node.py mode='local') — an unconfirmed remote node must stay
    the historical loud warning, not exit 1 on a healthy job."""
    c, handles = _mk_cluster(reached={0},
                             worker_states={0: "running", 1: None})
    # make node 1 look like it lives on another host
    for n in c.cluster_info:
        if n["executor_id"] == 1:
            n["host"] = "203.0.113.77"
    try:
        c.shutdown(grace_secs=1, timeout=60)  # must not raise
        assert "error" not in c.tf_status
    finally:
        for h in handles:
            h.shutdown()


def test_is_tpu_device_is_the_platform_name():
    """A device is a TPU exactly when its platform says so; everything
    gating on 'is this a TPU' (the pallas interpret default) relies on
    it."""
    from tensorflowonspark_tpu import device_info

    class FakeDev:
        def __init__(self, platform, kind):
            self.platform = platform
            self.device_kind = kind

    assert device_info.is_tpu_device(FakeDev("tpu", "TPU v5 lite"))
    assert not device_info.is_tpu_device(FakeDev("cpu", "cpu"))
    assert not device_info.is_tpu_device(FakeDev("gpu", "NVIDIA H100"))
    # the kind string decides nothing
    assert not device_info.is_tpu_device(FakeDev("cpu", "TPU v5 lite"))
    assert not device_info.is_tpu_device()  # the suite's default device


def test_backends_initialized_probe_exists_in_this_jax():
    """``device_info.backends_initialized`` is the one place that reads a
    private jax module (``jax._src.xla_bridge.backends_are_initialized``):
    fail loudly here if an upgrade takes it away."""
    import jax
    from jax._src import xla_bridge

    from tensorflowonspark_tpu import device_info

    assert callable(xla_bridge.backends_are_initialized)
    jax.devices()
    assert device_info.backends_initialized()
    with pytest.raises(RuntimeError, match="before JAX initializes"):
        device_info.pin_chips(0, 1)


def test_backends_initialized_while_jax_is_being_imported(monkeypatch):
    """A thread that polls (``profiler.start_server_when_backend_is_up``)
    while the main thread imports jax meets ``jax._src.xla_bridge`` in
    ``sys.modules`` before its body has run: no backend yet, and no
    ``AttributeError`` that would end the poller."""
    import sys
    import types

    from tensorflowonspark_tpu import device_info

    monkeypatch.setitem(sys.modules, "jax._src.xla_bridge",
                        types.ModuleType("jax._src.xla_bridge"))
    assert device_info.backends_initialized() is False


def _collect_feed_run(map_fun, rows, env, collect, chunk_size=6):
    """Spin one 2-executor SPARK-mode cluster under ``env``, train one epoch
    of ``rows`` through it, and return ``[collect(executor_dir), ...]`` plus
    the aggregated transport tally.  Artifacts must be read via ``collect``
    inside this call: ``b.stop()`` removes the executor workdirs."""
    import json
    import time

    b = backend.LocalBackend(2, env=env) if env else backend.LocalBackend(2)
    try:
        c = cluster.run(b, map_fun, tf_args=[], num_executors=2,
                        input_mode=InputMode.SPARK)
        c.train(backend.partition(rows, 4), num_epochs=1,
                chunk_size=chunk_size)
        c.shutdown()
        outs, fmts = [], {}
        for i in range(2):
            d = os.path.join(b.workdir_root, "executor-{}".format(i))
            # shutdown poisons the queues but does not wait for the training
            # process to return from map_fun: poll for its artifacts.
            # map_fun writes wire.json LAST, so once it parses, everything
            # it wrote before is complete.
            deadline = time.time() + 30
            while True:
                try:
                    with open(os.path.join(d, "wire.json")) as f:
                        per = json.load(f)
                    break
                except (OSError, ValueError):
                    if time.time() > deadline:
                        raise
                    time.sleep(0.1)
            outs.append(collect(d))
            for k, v in per.items():
                fmts[k] = fmts.get(k, 0) + v
        return outs, fmts
    finally:
        b.stop()


def test_wire_parity_framed_vs_disabled_shm():
    """Acceptance: the zero-copy framed ring path and the ring-less
    TFOS_DISABLE_SHM path must deliver element-identical rows end to end —
    the wire format is a transport, never a transform."""
    import json

    import numpy as np

    def map_fun(args, ctx):
        feed = ctx.get_data_feed()
        xs, ys = [], []
        while not feed.should_stop():
            arrays, count = feed.next_batch_arrays(6)
            if count:
                xs.append(arrays[0])
                ys.append(arrays[1])
        np.savez("rows.npz",
                 x=np.concatenate(xs) if xs else np.empty((0, 4), np.float32),
                 y=np.concatenate(ys) if ys else np.empty((0,), np.int64))
        with open("wire.json", "w") as f:
            json.dump(getattr(feed, "wire_formats", {}), f)

    rows = [(np.full(4, 3 * i + 1, np.float32), i) for i in range(24)]

    def collect(d):
        data = np.load(os.path.join(d, "rows.npz"))
        return data["x"], data["y"]

    def run(env):
        outs, fmts = _collect_feed_run(map_fun, rows, env, collect)
        x = np.concatenate([o[0] for o in outs])
        y = np.concatenate([o[1] for o in outs])
        order = np.argsort(y, kind="stable")  # labels are unique: a total
        return x[order], y[order], fmts       # order independent of which
                                              # executor got which partition

    x_framed, y_framed, fmt_framed = run(None)
    x_plain, y_plain, fmt_plain = run({"TFOS_DISABLE_SHM": "1"})

    np.testing.assert_array_equal(x_framed, x_plain)
    np.testing.assert_array_equal(y_framed, y_plain)
    assert y_framed.tolist() == list(range(24))
    # the disabled run must never have touched a ring
    assert set(fmt_plain) <= {"queue"}, fmt_plain
    if shmring.available():
        # uniform numeric rows on a ring host: every chunk took the frame
        assert fmt_framed.get("colv1"), fmt_framed
        assert "pickle" not in fmt_framed, fmt_framed


def test_wire_parity_object_chunks_on_ring():
    """Ragged rows can't be framed (rows_to_fields soft-fails), so on a
    ring host they travel as pickled object chunks on the SAME ring the
    framed records use — and must still match the ring-less run exactly."""
    import json

    def map_fun(args, ctx):
        feed = ctx.get_data_feed()
        items = []
        while not feed.should_stop():
            got = feed.next_batch(5)
            items.extend(got)
        # normalize: a single-row remainder chunk is trivially uniform, so
        # it may round-trip as an ndarray row (columnar path quirk shared
        # by every transport) — parity is about VALUES
        with open("items.json", "w") as f:
            json.dump(sorted([int(v) for v in it] for it in items), f)
        with open("wire.json", "w") as f:
            json.dump(getattr(feed, "wire_formats", {}), f)

    # variable-length rows: pack_columnar returns None -> object Chunk
    rows = [[i] * (1 + i % 3) for i in range(18)]

    def collect(d):
        with open(os.path.join(d, "items.json")) as f:
            return json.load(f)

    def run(env):
        outs, fmts = _collect_feed_run(map_fun, rows, env, collect,
                                       chunk_size=4)
        return sorted(sum(outs, [])), fmts

    items_framed, fmt_framed = run(None)
    items_plain, fmt_plain = run({"TFOS_DISABLE_SHM": "1"})

    assert items_framed == items_plain == sorted(rows)
    assert set(fmt_plain) <= {"queue"}, fmt_plain
    if shmring.available():
        # object chunks on a ring host take the pickled ring path (the
        # single-row remainder chunks may legitimately frame as colv1)
        assert fmt_framed.get("pickle"), fmt_framed


def test_initialize_distributed_refuses_processes_that_form_no_world(
        monkeypatch):
    """Several executors on one TPU host, each pinned to its own chip, stay
    worlds of one device whatever the rendezvous says (observed on a 2x2
    v5e): the join is refused with a clear error, not left to hang in the
    first collective."""
    import jax

    from tensorflowonspark_tpu import node

    joined = []
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: joined.append(kw))
    ctx = node.TPUNodeContext(
        2, "worker", 2, [], "file://", "/tmp", None, "localhost:1234",
        num_processes=4, process_id=2)
    with pytest.raises(RuntimeError, match="joined 4 processes.*world has 1"):
        ctx.initialize_distributed()
    assert joined[0]["num_processes"] == 4 and joined[0]["process_id"] == 2
    # a world that did form is left alone
    monkeypatch.setattr(jax, "process_count", lambda: 4)
    ctx.initialize_distributed()


def _bringup_map_fun(args, ctx):
    """Builds a small Trainer, takes one step from ``fit_feed`` and writes
    what ``counters_snapshot()`` says afterwards."""
    import json

    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu import train as train_mod

    def loss(params, batch, mask):
        err = (batch["x"] @ params["w"] - batch["y"]) ** 2 * mask
        return err.sum() / jnp.maximum(mask.sum(), 1.0), {}

    trainer = train_mod.Trainer(loss, {"w": jnp.zeros((2,))},
                                optax.sgd(0.1), batch_size=4)
    before = trainer.counters_snapshot()

    class OneBatch(object):
        def batches(self):
            yield ({"x": np.ones((4, 2), np.float32),
                    "y": np.ones((4,), np.float32)},
                   np.ones((4,), np.float32))

    trainer.fit_feed(OneBatch())
    with open("bringup.json", "w") as f:
        json.dump({"before": before, "after": trainer.counters_snapshot()},
                  f)
    if args and args[0] == "spark":
        feed = ctx.get_data_feed()
        while not feed.should_stop():
            feed.next_batch(4)


@pytest.mark.parametrize("mode", ["files", "spark"])
def test_bringup_account_crosses_driver_executor_and_trainer(mode):
    """The bring-up's account, always on (telemetry is off here): the
    driver's marks reach the executor through ``cluster_meta``, in SPARK
    mode the forked child goes on with its parent's, and once the first
    dispatch of ``fit_feed`` has returned ``counters_snapshot()`` tells
    nine phases that sum to ``bringup_wall_us`` to the microsecond."""
    import json

    from tensorflowonspark_tpu import telemetry

    b = backend.LocalBackend(1)
    try:
        c = cluster.run(b, _bringup_map_fun, tf_args=[mode], num_executors=1,
                        input_mode=(InputMode.SPARK if mode == "spark"
                                    else InputMode.FILES))
        # the driver's marks rode cluster_meta: two, `driver` then `spawn`
        assert [p for _, p in c.cluster_meta["bringup"]] == ["driver",
                                                             "spawn"]
        if mode == "spark":
            c.train(backend.partition(range(8), 2))
        c.shutdown()
        with open(os.path.join(b.workdir_root, "executor-0",
                               "bringup.json")) as f:
            told = json.load(f)
    finally:
        b.stop()
    # nothing of it before the first dispatch has returned
    assert not [k for k in told["before"] if k.startswith("bringup_")]
    after = told["after"]
    phases = {p: after["bringup_%s_us" % p] for p in telemetry.BRINGUP_PHASES}
    assert len(phases) == 9
    assert all(isinstance(v, int) and v >= 0 for v in phases.values()), phases
    assert sum(phases.values()) == after["bringup_wall_us"], phases
    assert phases["spawn"] > 0 and phases["rendezvous"] > 0, phases
    # the step program's making was in the first dispatch, and it is named
    assert phases["first_dispatch"] > 0 and phases["trainer_init"] > 0
    assert after["compile_programs"] >= 1
    assert after["compile_trace_us"] + after["compile_backend_us"] > 0
    assert "train_recompiles_total" not in after
