"""Worker program for the multi-process jax.distributed test harness.

Each test spawns N copies of this script (separate interpreters on
localhost, rank 0 hosting the coordinator) — the TPU-native equivalent of
the reference's Spark-Standalone separate-worker-process rig (reference
``test/README.md:10``, SURVEY §4.3) — and each rank runs one named scenario
exercising a ``jax.process_count() > 1`` code path:

- ``consensus``:   uneven end-of-data across hosts -> all stop together
- ``infeed``:      ShardedFeed assembles a global batch from per-process
                   local shards, including an uneven padded tail
- ``grouped``:     K-step group consensus degrades all hosts to single
                   mode in lock-step on uneven feeds
- ``drain``:       batches(drain='all') exact-eval dummies keep hosts
                   aligned until everyone is exhausted
- ``filefeed``:    FILES-mode FileFeed file sharding across processes
- ``checkpoint``:  orbax collective save/restore with every host entering
                   the save (non-chief included)

Usage: python multiproc_worker.py <scenario> <rank> <world> <port> <tmpdir>
"""

import os
import sys


def _arm_env():
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=2")
    os.environ["XLA_FLAGS"] = " ".join(flags)


def scenario_consensus(rank, world, tmpdir):
    import jax

    from tensorflowonspark_tpu.parallel import collectives, mesh as mesh_mod

    assert jax.process_count() == world, jax.process_count()
    mesh = mesh_mod.build_mesh()
    # rank r pretends to have 2 + r steps of data: everyone must stop after
    # min_r(2 + r) = 2 full steps (the exact cross-host end-of-data barrier
    # replacing the reference's 90%-of-steps heuristic, mnist_spark.py:58-66)
    results = []
    for step in range(2 + world + 1):
        has_data = step < 2 + rank
        ok = collectives.end_of_data_consensus(mesh, has_data)
        results.append(ok)
        if not ok:
            break
    assert results == [True, True, False], (rank, results)
    print("consensus ok", rank, results)


def scenario_infeed(rank, world, tmpdir):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu import manager
    from tensorflowonspark_tpu.datafeed import DataFeed
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod
    from tensorflowonspark_tpu.parallel.infeed import ShardedFeed

    mesh = mesh_mod.build_mesh()
    global_batch = 8 * world
    assert mesh_mod.local_batch_size(mesh, global_batch) == 8

    # rank 0 gets 12 rows, other ranks 16: step 1 is full, step 2 has a
    # padded tail on rank 0, step 3 hits end-of-feed everywhere.
    n_rows = 12 if rank == 0 else 16
    rows = [[float(rank * 100 + i)] for i in range(n_rows)]
    mgr = manager.start(b"mp-infeed-%d" % rank, ["input"])
    q = mgr.get_queue("input")
    for r in rows:
        q.put(r)
    q.put(None)

    sf = ShardedFeed(DataFeed(mgr), mesh, global_batch, prefetch=2)
    mask_sums = []
    batch_sums = []
    for batch, mask in sf.batches():
        # global reductions over the multi-process sharded array
        mask_sums.append(float(jax.jit(jnp.sum)(mask)))
        batch_sums.append(float(jax.jit(jnp.sum)(batch * mask[:, None])))
    mgr.shutdown()

    expected_mask = [8.0 * world, 12.0 if world == 2 else float(4 + 8 * (world - 1))]
    assert mask_sums == expected_mask, (rank, mask_sums, expected_mask)
    # sum of all real rows across ranks
    total = sum(sum(float(r * 100 + i) for i in range(12 if r == 0 else 16))
                for r in range(world))
    assert abs(sum(batch_sums) - total) < 1e-3, (rank, batch_sums, total)
    print("infeed ok", rank, mask_sums)


def scenario_grouped(rank, world, tmpdir):
    """grouped_batches across hosts with UNEVEN feeds: rank 0 runs out of
    full K-groups first, so the group consensus degrades every host to
    single-step mode in lock-step — rank 1 must split its already-assembled
    group back into singles via the jitted multi-host-safe slice."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import manager
    from tensorflowonspark_tpu.datafeed import DataFeed
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod
    from tensorflowonspark_tpu.parallel.infeed import ShardedFeed

    mesh = mesh_mod.build_mesh()
    global_batch = 8 * world
    # rank 0: 3 full local batches (1 group of 2 + 1 flushed single);
    # others: 5 full batches (2 groups + 1 pending flushed single).
    n_rows = 24 if rank == 0 else 40
    rows = [[float(rank * 1000 + i)] for i in range(n_rows)]
    mgr = manager.start(b"mp-grouped-%d" % rank, ["input"])
    q = mgr.get_queue("input")
    for r in rows:
        q.put(r)
    q.put(None)

    sf = ShardedFeed(DataFeed(mgr), mesh, global_batch, prefetch=2)
    kinds = []
    mask_sums = []
    for kind, batch, mask in sf.grouped_batches(2):
        kinds.append(kind)
        mask_sums.append(float(jax.jit(jnp.sum)(mask)))
    mgr.shutdown()

    # group 1 agreed everywhere; the second group attempt disagrees (rank 0
    # holds a flushed single) -> everyone degrades; one aligned single step
    # runs; then rank 0 hits end-of-feed and all stop together.
    assert kinds == ["multi", "single"], (rank, kinds)
    assert mask_sums == [16.0 * world, 8.0 * world], (rank, mask_sums)
    print("grouped ok", rank, kinds, mask_sums)


def scenario_drain_all(rank, world, tmpdir):
    """batches(drain='all') with uneven feeds: the short host emits
    zero-mask dummies until the long host finishes — every real row on
    every host is consumed (exact evaluation), unlike drain='any'."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import manager
    from tensorflowonspark_tpu.datafeed import DataFeed
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod
    from tensorflowonspark_tpu.parallel.infeed import ShardedFeed

    mesh = mesh_mod.build_mesh()
    global_batch = 8 * world
    n_rows = 8 if rank == 0 else 20   # rank 0: 1 batch; others: 2.5 batches
    rows = [[float(rank * 1000 + i)] for i in range(n_rows)]
    mgr = manager.start(b"mp-drain-%d" % rank, ["input"])
    q = mgr.get_queue("input")
    for r in rows:
        q.put(r)
    q.put(None)

    sf = ShardedFeed(DataFeed(mgr), mesh, global_batch, prefetch=2)
    mask_sums = []
    for batch, mask in sf.batches(drain="all"):
        mask_sums.append(float(jax.jit(jnp.sum)(mask)))
    mgr.shutdown()

    # per-step real-row mask totals: step1 full everywhere (8*world), then
    # rank 0 is exhausted and contributes dummies (0) while the others run
    # a full batch (step2) and a padded 4-row tail (step3).
    expected = [8.0 * world, 8.0 * (world - 1), 4.0 * (world - 1)]
    assert mask_sums == expected, (rank, mask_sums, expected)
    total = sum(mask_sums)
    assert total == 8 + 20 * (world - 1), (rank, total)
    print("drain ok", rank, mask_sums)


def scenario_filefeed(rank, world, tmpdir):
    """FILES mode multi-host: data.FileFeed shards files by process and the
    ShardedFeed consensus keeps hosts aligned — every row lands exactly
    once across the world."""
    import time

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import data as data_mod, dfutil
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod
    from tensorflowonspark_tpu.parallel.infeed import ShardedFeed

    shard_dir = os.path.join(tmpdir, "shards")
    marker = os.path.join(tmpdir, "staged")
    if rank == 0:
        rows = dfutil.Rows([{"v": float(i)} for i in range(40)],
                           schema={"v": "float32"})
        dfutil.save_as_tfrecords(rows, shard_dir, num_shards=4)
        open(marker, "w").close()
    else:
        deadline = time.time() + 60
        while not os.path.exists(marker):
            assert time.time() < deadline, "staging never appeared"
            time.sleep(0.1)

    import numpy as np

    mesh = mesh_mod.build_mesh()
    feed = data_mod.FileFeed(data_mod.list_shards(shard_dir))  # shard=True
    sf = ShardedFeed(
        feed, mesh, global_batch_size=8 * world, prefetch=2,
        transform=lambda cols: np.asarray(cols["v"], np.float32))

    sums = []
    mask_sums = []
    for batch, mask in sf.batches():
        sums.append(float(jax.jit(lambda b, m: (b * m).sum())(batch, mask)))
        mask_sums.append(float(jax.jit(jnp.sum)(mask)))
    # 40 rows over the world: world=2 -> 20/host -> [full, full, padded 4]
    assert mask_sums == [8.0 * world, 8.0 * world, 4.0 * world], (
        rank, mask_sums)
    assert abs(sum(sums) - sum(range(40))) < 1e-3, (rank, sums)
    print("filefeed ok", rank, mask_sums)


def scenario_checkpoint(rank, world, tmpdir):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu import checkpoint as ckpt_mod
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.build_mesh()
    state = {"w": jax.device_put(jnp.arange(4.0), mesh_mod.replicated(mesh)),
             "step": jnp.asarray(7)}
    ckpt_dir = os.path.join(tmpdir, "ckpt")
    # every host enters the collective save; orbax routes the write to the
    # primary host (the discipline checkpoint.py documents)
    mgr = ckpt_mod.CheckpointManager(ckpt_dir, is_chief=(rank == 0))
    assert mgr.maybe_save(3, state, force=True)
    mgr.wait_until_finished()

    abstract = {"w": np.zeros(4, np.float32), "step": np.asarray(0)}
    restored, step = mgr.restore_latest(abstract)
    assert step == 3, step
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(4.0))
    mgr.close()
    print("checkpoint ok", rank)




def scenario_storm(rank, world, tmpdir):
    """The flaky-feed storm: every degrade-adjacent
    mechanism at once — grouped_batches K-group consensus degrade, prefetch
    double-buffering, the native shm-ring transport, and an EARLY
    ``terminate()`` while other hosts still hold queued rows — on an
    uneven world (run with world=3)."""
    import pickle
    import threading

    from tensorflowonspark_tpu import manager, marker, shmring
    from tensorflowonspark_tpu.datafeed import DataFeed
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod
    from tensorflowonspark_tpu.parallel.infeed import ShardedFeed

    assert shmring.available(), "shm ring must be the transport under test"
    mesh = mesh_mod.build_mesh()
    global_batch = 8 * world
    # rank 0: 3 local batches (1 full K=2 group, then a flushed single ->
    # every host degrades in lock-step); others: 10 batches (7+ still
    # unconsumed at terminate time, some of them sitting in the ring).
    n_batches = 3 if rank == 0 else 10
    mgr = manager.start(b"mp-storm-%d" % rank, ["input"])
    q = mgr.get_queue("input")
    ring = shmring.Ring.create_or_attach("mpstorm{}".format(rank))

    def feeder():
        for b in range(n_batches):
            rows = [[float(rank * 10000 + b * 8 + i)] for i in range(8)]
            chunk = marker.pack_columnar(rows)
            assert chunk is not None
            data = pickle.dumps(chunk, protocol=pickle.HIGHEST_PROTOCOL)
            assert ring.put_bytes(data, timeout_secs=120)
            q.put(marker.ShmChunk(ring.name, 8), block=True)
        q.put(None)

    t = threading.Thread(target=feeder, daemon=True)
    t.start()

    sf = ShardedFeed(DataFeed(mgr), mesh, global_batch, prefetch=2)
    kinds = []
    for kind, batch, mask in sf.grouped_batches(2):
        kinds.append(kind)
        if kind == "single":
            break  # stop mid-stream: long ranks still have rows queued
    # single-consumer discipline: terminate joins the prefetch thread then
    # drains the queue AND the ring so the feeder can finish its puts
    sf.terminate()
    t.join(timeout=120)
    assert not t.is_alive(), "feeder wedged: terminate failed to drain"
    mgr.shutdown()
    assert kinds == ["multi", "single"], (rank, kinds)
    print("storm ok", rank, kinds)


SCENARIOS = {
    "consensus": scenario_consensus,
    "infeed": scenario_infeed,
    "grouped": scenario_grouped,
    "drain": scenario_drain_all,
    "filefeed": scenario_filefeed,
    "storm": scenario_storm,
    "checkpoint": scenario_checkpoint,
}


def main():
    scenario, rank, world, port, tmpdir = sys.argv[1:6]
    rank, world = int(rank), int(world)
    _arm_env()
    import jax

    jax.distributed.initialize(
        coordinator_address="127.0.0.1:{}".format(port),
        num_processes=world, process_id=rank)
    assert jax.process_count() == world
    SCENARIOS[scenario](rank, world, tmpdir)


if __name__ == "__main__":
    main()
