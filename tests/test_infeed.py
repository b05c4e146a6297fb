"""ShardedFeed tests, including the review regressions: partial-final-batch
end-of-feed must terminate (not block), preprocess must apply in dict mode,
pad_final=False must drop tails, prefetch must not consume past early exit."""

import numpy as np
import pytest

import jax
import optax

from tensorflowonspark_tpu import manager
from tensorflowonspark_tpu.datafeed import DataFeed
from tensorflowonspark_tpu.parallel import build_mesh
from tensorflowonspark_tpu.parallel.infeed import ShardedFeed


@pytest.fixture
def mgr():
    m = manager.start(b"infeed-test", ["input", "output", "error"])
    yield m
    m.shutdown()


def _fill(m, rows, end=True):
    q = m.get_queue("input")
    for r in rows:
        q.put(r)
    if end:
        q.put(None)


def test_partial_final_batch_terminates(mgr):
    """12 rows, local batch 8: full batch + padded 4-row batch, then STOP —
    must not block on a queue whose None sentinel was already consumed."""
    _fill(mgr, [[float(i)] for i in range(12)])
    feed = DataFeed(mgr)
    sf = ShardedFeed(feed, build_mesh(), global_batch_size=8, prefetch=0)
    out = list(sf.batches())
    assert len(out) == 2
    batch0, mask0 = out[0]
    batch1, mask1 = out[1]
    assert np.asarray(mask0).sum() == 8
    assert np.asarray(mask1).sum() == 4          # padded tail, masked
    assert np.asarray(batch1).shape == (8, 1)    # padded to full local batch


def test_partial_final_batch_with_prefetch(mgr):
    _fill(mgr, [[float(i)] for i in range(12)])
    sf = ShardedFeed(DataFeed(mgr), build_mesh(), global_batch_size=8,
                     prefetch=2)
    out = list(sf.batches())
    assert [int(np.asarray(m).sum()) for _, m in out] == [8, 4]


def test_pad_final_false_drops_tail(mgr):
    _fill(mgr, [[float(i)] for i in range(12)])
    sf = ShardedFeed(DataFeed(mgr), build_mesh(), global_batch_size=8,
                     pad_final=False, prefetch=0)
    out = list(sf.batches())
    assert len(out) == 1
    assert np.asarray(out[0][1]).sum() == 8


def test_preprocess_applies_in_dict_mode(mgr):
    _fill(mgr, [([1.0], 0), ([2.0], 1)] * 4)
    feed = DataFeed(mgr, input_mapping={"a_x": "x", "b_y": "y"})

    def preprocess(arrays):
        return {"x": np.asarray(arrays["x"]) * 100.0,
                "y": np.asarray(arrays["y"])}

    sf = ShardedFeed(feed, build_mesh(), global_batch_size=8,
                     preprocess=preprocess, prefetch=0)
    (batch, mask), = list(sf.batches())
    assert float(np.asarray(batch["x"]).max()) == 200.0


def test_early_exit_stops_prefetch_consumption(mgr):
    """Breaking out of batches() must not let the prefetch thread drain the
    whole queue behind the consumer's back."""
    import time

    _fill(mgr, [[float(i)] for i in range(64)], end=False)
    sf = ShardedFeed(DataFeed(mgr), build_mesh(), global_batch_size=8,
                     prefetch=1)
    gen = sf.batches()
    next(gen)
    gen.close()          # early exit (e.g. max_steps)
    time.sleep(0.5)
    # 8 consumed by the yielded batch; at most ~2 more may sit in prefetch
    remaining = mgr.get_queue("input").qsize()
    assert remaining >= 64 - 8 - 3 * 8


def test_terminate_joins_prefetch_before_drain(mgr):
    """Regression (advisor r1): terminate() while the prefetch thread is
    live must stop + join it BEFORE draining the queue — two concurrent
    consumers can double-task_done (ValueError) or desync the shm ring."""
    _fill(mgr, [[float(i)] for i in range(64)], end=False)
    sf = ShardedFeed(DataFeed(mgr), build_mesh(), global_batch_size=8,
                     prefetch=2)
    gen = sf.batches()
    next(gen)
    sf.terminate()           # prefetch thread still running — must be joined
    t = sf._prefetch_thread
    assert t is not None and not t.is_alive()
    gen.close()
    assert mgr.get("state") == "terminating"


def test_terminate_with_prefetch_blocked_on_empty_queue(mgr):
    """terminate() when the prefetch thread is parked in a blocking get
    (no more data, no sentinel yet) must interrupt it, not hang the join."""
    _fill(mgr, [[float(i)] for i in range(8)], end=False)  # exactly one batch
    sf = ShardedFeed(DataFeed(mgr), build_mesh(), global_batch_size=8,
                     prefetch=2)
    gen = sf.batches()
    next(gen)                # prefetch now blocks on the empty queue
    import time

    time.sleep(0.3)
    t0 = time.time()
    sf.terminate()
    assert time.time() - t0 < 10
    t = sf._prefetch_thread
    assert t is not None and not t.is_alive()
    gen.close()


def test_trainer_fit_feed_end_to_end(mgr):
    """fit_feed over a ShardedFeed with a partial tail trains and returns stats."""
    rng = np.random.RandomState(0)
    rows = [([float(x) for x in rng.rand(2)],) for _ in range(20)]
    rows = [(r[0], float(np.dot(r[0], [3.14, 1.618]))) for r in rows]
    _fill(mgr, rows)
    feed = DataFeed(mgr, input_mapping={"a_x": "x", "b_y": "y"})
    mesh = build_mesh()
    sf = ShardedFeed(feed, mesh, global_batch_size=8, prefetch=0)

    from tensorflowonspark_tpu.train import Trainer
    import jax.numpy as jnp

    def loss(params, batch, mask):
        pred = jnp.asarray(batch["x"]) @ params["w"]
        err = (pred - jnp.asarray(batch["y"])) ** 2 * mask
        return err.sum() / jnp.maximum(mask.sum(), 1.0), {}

    tr = Trainer(loss, {"w": jnp.zeros((2,))}, optax.adam(0.1), mesh=mesh,
                 batch_size=8, log_steps=2)
    stats = tr.fit_feed(sf)
    assert stats["global_steps"] == 3  # 8 + 8 + 4(padded)
    assert "loss" in stats


def test_grouped_batches_full_groups(mgr):
    """32 rows, batch 8, k=2 -> two ('multi', stack, masks) groups with
    leaves shaped (2, 8, ...)."""
    _fill(mgr, [[float(i)] for i in range(32)])
    sf = ShardedFeed(DataFeed(mgr), build_mesh(), global_batch_size=8,
                     prefetch=0)
    out = list(sf.grouped_batches(2))
    assert [kind for kind, _, _ in out] == ["multi", "multi"]
    kind, stack, masks = out[0]
    assert np.asarray(stack).shape == (2, 8, 1)
    assert np.asarray(masks).shape == (2, 8)
    assert np.asarray(masks).sum() == 16


def test_grouped_batches_tail_degrades_to_singles(mgr):
    """20 rows, batch 8, k=2 -> one full group (16 rows) then a padded
    4-row single; the mode switch is permanent."""
    _fill(mgr, [[float(i)] for i in range(20)])
    sf = ShardedFeed(DataFeed(mgr), build_mesh(), global_batch_size=8,
                     prefetch=2)
    out = list(sf.grouped_batches(2))
    assert [kind for kind, _, _ in out] == ["multi", "single"]
    _, batch, mask = out[1]
    assert np.asarray(batch).shape == (8, 1)
    assert np.asarray(mask).sum() == 4


def test_grouped_batches_pending_flush(mgr):
    """k=4 with only 2 full batches available: the pending group can't fill,
    so both batches arrive as singles (exact same rows, no loss)."""
    _fill(mgr, [[float(i)] for i in range(16)])
    sf = ShardedFeed(DataFeed(mgr), build_mesh(), global_batch_size=8,
                     prefetch=0)
    out = list(sf.grouped_batches(4))
    assert [kind for kind, _, _ in out] == ["single", "single"]
    got = np.concatenate([np.asarray(b).ravel() for _, b, _ in out])
    np.testing.assert_array_equal(np.sort(got), np.arange(16, dtype=np.float32))


def test_grouped_device_vs_host_assembly_parity(mgr):
    """The device-stack assembler must build bit-identical groups to the
    host np.stack path: same rows -> equal stacks, masks, and kinds."""
    rows = [[float(i)] for i in range(20)]
    feeds = {}
    for mode in ("device", "host"):
        m2 = manager.start(b"infeed-parity-" + mode.encode(),
                           ["input", "output", "error"])
        try:
            _fill(m2, rows)
            sf = ShardedFeed(DataFeed(m2), build_mesh(), global_batch_size=8,
                             prefetch=0, group_assembly=mode)
            assert sf.group_assembly == mode
            feeds[mode] = list(sf.grouped_batches(2))
        finally:
            m2.shutdown()
    assert [k for k, _, _ in feeds["device"]] == \
        [k for k, _, _ in feeds["host"]] == ["multi", "single"]
    for (_, bd, md), (_, bh, mh) in zip(feeds["device"], feeds["host"]):
        np.testing.assert_array_equal(np.asarray(bd), np.asarray(bh))
        np.testing.assert_array_equal(np.asarray(md), np.asarray(mh))


def test_host_assembly_tail_degrades_to_singles(mgr):
    """The degrade-to-singles switch works in host-stack mode too (the
    default device path is covered by the tests above)."""
    _fill(mgr, [[float(i)] for i in range(20)])
    sf = ShardedFeed(DataFeed(mgr), build_mesh(), global_batch_size=8,
                     prefetch=2, group_assembly="host")
    assert not sf.group_donation_safe    # host mode reuses mask stacks
    out = list(sf.grouped_batches(2))
    assert [kind for kind, _, _ in out] == ["multi", "single"]
    got = np.concatenate(
        [np.asarray(b).reshape(-1, 8)[np.asarray(m).reshape(-1, 8) > 0]
         for _, b, m in out])
    np.testing.assert_array_equal(np.sort(got),
                                  np.arange(20, dtype=np.float32))


def test_device_assembly_counters_and_donation(mgr):
    """Device assembly tallies train_group_assemble_us, keeps the per-batch
    put tallies alive, and reports donation-safe stacks."""
    _fill(mgr, [[float(i)] for i in range(32)])
    sf = ShardedFeed(DataFeed(mgr), build_mesh(), global_batch_size=8,
                     prefetch=0)
    assert sf.group_assembly == "device"   # default
    assert sf.group_donation_safe
    out = list(sf.grouped_batches(2))
    assert [kind for kind, _, _ in out] == ["multi", "multi"]
    snap = sf.counters_snapshot()
    assert snap["train_group_assemble_us"] > 0
    assert snap["infeed_put_us"] > 0       # per-batch transfers still tallied
    assert snap["infeed_batches"] == 4


def test_apply_knob_retunes_group_size_on_boundary(mgr):
    """A train_steps_per_call push lands at the NEXT group-fill start: the
    first group keeps the seeded K, later groups use the new K."""
    _fill(mgr, [[float(i)] for i in range(48)])   # 6 batches of 8
    sf = ShardedFeed(DataFeed(mgr), build_mesh(), global_batch_size=8,
                     prefetch=0)
    it = sf.grouped_batches(2)
    kind, stack, _ = next(it)
    assert kind == "multi" and np.asarray(stack).shape[0] == 2
    assert sf.apply_knob("train_steps_per_call", 4)
    shapes = [np.asarray(s).shape[0] for kind, s, _ in it if kind == "multi"]
    assert shapes == [4]                          # remaining 4 batches regroup
    got = np.asarray(stack).ravel()
    np.testing.assert_array_equal(np.sort(got),
                                  np.arange(16, dtype=np.float32))


def test_apply_knob_steps_per_call_refused_multiprocess(mgr):
    """Per-host K retunes are refused on multi-process meshes — a transient
    knob skew would desync the SPMD group lock-step."""
    _fill(mgr, [])
    sf = ShardedFeed(DataFeed(mgr), build_mesh(), global_batch_size=8,
                     prefetch=0)
    sf._num_processes = 2
    assert sf.apply_knob("train_steps_per_call", 4) is False
    assert sf._group_k_target is None


def test_fit_feed_steps_per_call_trains_all_steps(mgr):
    """fit_feed(steps_per_call=2) consumes the same data as single-step mode
    and reports the same step count."""
    rng = np.random.RandomState(0)
    rows = []
    for _ in range(40):
        x = [float(v) for v in rng.rand(2)]
        rows.append((x, float(np.dot(x, [3.14, 1.618]))))
    _fill(mgr, rows)
    feed = DataFeed(mgr, input_mapping={"a_x": "x", "b_y": "y"})
    mesh = build_mesh()
    sf = ShardedFeed(feed, mesh, global_batch_size=8, prefetch=2)

    from tensorflowonspark_tpu.train import Trainer
    import jax.numpy as jnp

    def loss(params, batch, mask):
        pred = jnp.asarray(batch["x"]) @ params["w"]
        err = (pred - jnp.asarray(batch["y"])) ** 2 * mask
        return err.sum() / jnp.maximum(mask.sum(), 1.0), {}

    tr = Trainer(loss, {"w": jnp.zeros((2,))}, optax.adam(0.1), mesh=mesh,
                 batch_size=8, log_steps=2)
    stats = tr.fit_feed(sf, steps_per_call=2)
    assert stats["global_steps"] == 5  # 40 rows / batch 8: 2 groups + 1 single
    assert "loss" in stats


def test_fit_feed_on_steps_hook(mgr):
    """on_steps fires once per dispatch with the running step count — the
    periodic-checkpoint hook."""
    rng = np.random.RandomState(0)
    rows = []
    for _ in range(32):
        x = [float(v) for v in rng.rand(2)]
        rows.append((x, float(np.dot(x, [3.14, 1.618]))))
    _fill(mgr, rows)
    feed = DataFeed(mgr, input_mapping={"a_x": "x", "b_y": "y"})
    mesh = build_mesh()
    sf = ShardedFeed(feed, mesh, global_batch_size=8, prefetch=0)

    from tensorflowonspark_tpu.train import Trainer
    import jax.numpy as jnp

    def loss(params, batch, mask):
        pred = jnp.asarray(batch["x"]) @ params["w"]
        err = (pred - jnp.asarray(batch["y"])) ** 2 * mask
        return err.sum() / jnp.maximum(mask.sum(), 1.0), {}

    tr = Trainer(loss, {"w": jnp.zeros((2,))}, optax.sgd(0.1), mesh=mesh,
                 batch_size=8, log_steps=10)
    seen = []
    tr.fit_feed(sf, steps_per_call=2, on_steps=seen.append)
    assert seen == [2, 4]  # one call per 2-step group dispatch


def test_fit_feed_steps_per_call_env_default(mgr, monkeypatch):
    """TFOS_STEPS_PER_CALL supplies the group size when the caller leaves
    steps_per_call at 1, and the megastep stats block records the mode."""
    monkeypatch.setenv("TFOS_STEPS_PER_CALL", "2")
    rng = np.random.RandomState(0)
    rows = []
    for _ in range(32):
        x = [float(v) for v in rng.rand(2)]
        rows.append((x, float(np.dot(x, [3.14, 1.618]))))
    _fill(mgr, rows)
    feed = DataFeed(mgr, input_mapping={"a_x": "x", "b_y": "y"})
    mesh = build_mesh()
    sf = ShardedFeed(feed, mesh, global_batch_size=8, prefetch=0)

    from tensorflowonspark_tpu.train import Trainer
    import jax.numpy as jnp

    def loss(params, batch, mask):
        pred = jnp.asarray(batch["x"]) @ params["w"]
        err = (pred - jnp.asarray(batch["y"])) ** 2 * mask
        return err.sum() / jnp.maximum(mask.sum(), 1.0), {}

    tr = Trainer(loss, {"w": jnp.zeros((2,))}, optax.sgd(0.1), mesh=mesh,
                 batch_size=8, log_steps=10)
    stats = tr.fit_feed(sf)                       # steps_per_call left at 1
    assert stats["global_steps"] == 4
    mega = stats["megastep"]
    assert mega["steps_per_call"] == 2            # env took effect
    assert mega["steps_per_call_last"] == 2
    assert mega["group_assembly"] == "device"
    # default Trainer donates state, device assembly is donation-safe
    assert mega["donate_state"] is True
    assert mega["donate_batches"] is True


def test_trainer_evaluate_exact(mgr):
    """Trainer.evaluate: mask-weighted metric means over a drain='all'
    feed, padded tail included exactly."""
    rows = [([float(i), 0.0], float(i)) for i in range(20)]  # y = x[0]
    _fill(mgr, rows)
    feed = DataFeed(mgr, input_mapping={"a_x": "x", "b_y": "y"})
    mesh = build_mesh()
    sf = ShardedFeed(feed, mesh, global_batch_size=8, prefetch=0)

    from tensorflowonspark_tpu.train import Trainer
    import jax.numpy as jnp

    def loss(params, batch, mask):
        pred = jnp.asarray(batch["x"]) @ params["w"]
        err = (pred - jnp.asarray(batch["y"])) ** 2 * mask
        return err.sum() / jnp.maximum(mask.sum(), 1.0), {}

    tr = Trainer(loss, {"w": jnp.asarray([1.0, 0.0])}, optax.sgd(0.1),
                 mesh=mesh, batch_size=8)

    def metric_fn(params, batch, mask):
        pred = jnp.asarray(batch["x"]) @ params["w"]
        err2 = ((pred - jnp.asarray(batch["y"])) ** 2 * mask).sum()
        return {"mse": err2, "pred_sum": (pred * mask).sum()}, mask.sum()

    out = tr.evaluate(sf, metric_fn)
    # w = [1, 0] predicts y exactly: mse 0; mean prediction = mean(0..19)
    assert out["mse"] == 0.0
    np.testing.assert_allclose(out["pred_sum"], np.mean(range(20)),
                               rtol=1e-6)


# -- device-resident step loop (round 8) -------------------------------------


def test_batches_device_resident_under_transfer_guard(mgr):
    """Every leaf batches() yields is already a sharded jax.Array: consuming
    them under an h2d transfer guard performs no implicit transfer (the
    infeed's own explicit puts run before the guard scope)."""
    import jax

    _fill(mgr, [[float(i)] for i in range(16)])
    sf = ShardedFeed(DataFeed(mgr), build_mesh(), global_batch_size=8,
                     prefetch=2)
    out = list(sf.batches())
    assert len(out) == 2
    consume = jax.jit(lambda b, m: (b[:, 0] * m).sum())
    with jax.transfer_guard_host_to_device("disallow"):
        for batch, mask in out:
            assert isinstance(batch, jax.Array)
            assert isinstance(mask, jax.Array)
            float(consume(batch, mask))  # d2h read stays legal: h2d-only


def test_fit_feed_transfer_guard_catches_host_batch():
    """Regression pin for the MFU story: a feed handing HOST numpy arrays
    to the dispatch loop is a hard error under the guard, not a silent
    per-step device_put."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.train import Trainer

    class HostFeed:
        def batches(self):
            for _ in range(2):
                yield (np.zeros((8, 2), np.float32),
                       np.ones((8,), np.float32))

    def loss(params, batch, mask):
        pred = batch @ params["w"]
        return (pred ** 2 * mask).sum(), {}

    tr = Trainer(loss, {"w": jnp.zeros((2,))}, optax.sgd(0.1),
                 mesh=build_mesh(), batch_size=8)
    with pytest.raises(Exception, match="host-to-device"):
        tr.fit_feed(HostFeed(), transfer_guard="disallow")


def test_fit_feed_guard_env_clean_on_sharded_feed(mgr, monkeypatch):
    """TFOS_TRANSFER_GUARD=disallow turns the guard on without code changes,
    and the real ShardedFeed path passes it clean — including first-dispatch
    compilation; the returned stats carry the overlap counters."""
    from tensorflowonspark_tpu import train as train_mod

    monkeypatch.setenv(train_mod.TRANSFER_GUARD_ENV, "disallow")
    rows = [([float(i), 1.0], float(i)) for i in range(24)]
    _fill(mgr, rows)
    feed = DataFeed(mgr, input_mapping={"a_x": "x", "b_y": "y"})
    mesh = build_mesh()
    sf = ShardedFeed(feed, mesh, global_batch_size=8, prefetch=2)

    import jax.numpy as jnp

    def loss(params, batch, mask):
        pred = jnp.asarray(batch["x"]) @ params["w"]
        err = (pred - jnp.asarray(batch["y"])) ** 2 * mask
        return err.sum() / jnp.maximum(mask.sum(), 1.0), {}

    tr = train_mod.Trainer(loss, {"w": jnp.zeros((2,))}, optax.sgd(0.1),
                           mesh=mesh, batch_size=8)
    stats = tr.fit_feed(sf)
    ov = stats["overlap"]
    assert ov["dispatch_count"] == 3
    assert ov["infeed_batches"] == 3
    assert ov["infeed_put_us"] > 0
    assert ov["infeed_assembly_us"] > 0
    assert ov["dispatch_gap_us"] > 0  # 2 measured gaps (first has no prev)
    assert ov["dispatch_gap_us_hwm"] <= ov["dispatch_gap_us"]


def test_terminate_joins_prefetch_parked_in_feed_call():
    """terminate() while the prefetch thread is parked inside the FEED's own
    blocking call (not the queue get) must re-interrupt and join within the
    bounded deadline — no leaked thread, no skipped drain."""
    import threading
    import time

    class SlowFeed:
        def __init__(self):
            self.calls = 0
            self.evt = threading.Event()
            self.terminated = False

        def should_stop(self):
            return False

        def next_batch_arrays(self, n):
            self.calls += 1
            if self.calls > 1:
                self.evt.wait(30)   # parked until interrupt()
                return np.zeros((0, 1), np.float32), 0
            return np.ones((n, 1), np.float32), n

        def interrupt(self):
            self.evt.set()

        def terminate(self):
            self.terminated = True

    feed = SlowFeed()
    sf = ShardedFeed(feed, build_mesh(), global_batch_size=8, prefetch=2)
    gen = sf.batches()
    next(gen)                       # prefetch thread now parked in the feed
    t0 = time.time()
    sf.terminate()
    assert time.time() - t0 < 10
    t = sf._prefetch_thread
    assert t is not None and not t.is_alive()
    assert feed.terminated          # drain ran: the join succeeded
    gen.close()


def test_prefetch_depth_from_env(mgr, monkeypatch):
    from tensorflowonspark_tpu.parallel import infeed as infeed_mod

    monkeypatch.setenv(infeed_mod.PREFETCH_ENV, "5")
    sf = ShardedFeed(DataFeed(mgr), build_mesh(), global_batch_size=8)
    assert sf._prefetch_depth == 5
    monkeypatch.delenv(infeed_mod.PREFETCH_ENV)
    sf2 = ShardedFeed(DataFeed(mgr), build_mesh(), global_batch_size=8)
    assert sf2._prefetch_depth == infeed_mod.DEFAULT_PREFETCH
    assert ShardedFeed(DataFeed(mgr), build_mesh(), global_batch_size=8,
                       prefetch=0)._prefetch_depth == 0


# -- the hand-back of batch buffers (ISSUE 33) --------------------------------

def _rows(n, width=4):
    return [(np.full((width,), i, np.float32), i) for i in range(n)]


def _aliased(device_array, host_arrays):
    """Whether a CPU device buffer IS memory of one of ``host_arrays``."""
    spans = [(a.ctypes.data, a.ctypes.data + a.nbytes) for a in host_arrays]
    return any(lo <= s.data.unsafe_buffer_pointer() < hi
               for s in device_array.addressable_shards for lo, hi in spans)


def _file_feed(rows, **kw):
    """The same tuple rows under FILES: a FileFeed over one 'file'."""
    from tensorflowonspark_tpu import data as data_mod

    return data_mod.FileFeed(["rows"], row_reader=lambda path: iter(rows),
                             shard=False, **kw)


@pytest.mark.parametrize("prefetch", [0, 2])
@pytest.mark.parametrize("transform", ["views", "copies"])
@pytest.mark.parametrize("source", ["DataFeed", "FileFeed"])
def test_delivered_batches_keep_their_rows_while_buffers_go_round(
        mgr, prefetch, transform, source):
    """On the CPU a device array may BE the host buffer: such a buffer is
    never handed back, any other is, and either way a batch delivered
    earlier still holds its own rows many batches later."""
    if source == "DataFeed":
        _fill(mgr, _rows(8 * 9))
        feed = DataFeed(mgr)
    else:
        feed = _file_feed(_rows(8 * 9))
    back = []           # the first column of every batch the feed took back
    release = feed.release

    def spy(arrays):
        took = release(arrays)
        if took:
            back.append(arrays[0])
        return took

    feed.release = spy
    if transform == "views":
        def fn(cols):
            return {"x": cols[0][:, :2], "y": cols[1]}
    else:
        def fn(cols):
            return {"x": cols[0] * 2.0, "y": cols[1].astype(np.int32)}
    sf = ShardedFeed(feed, build_mesh(), global_batch_size=8, transform=fn,
                     prefetch=prefetch)
    lent = []           # (the feed's arrays, the device batch made of them)
    shard = sf._shard

    def spy_shard(arrays, count, mine):
        batch, mask = shard(arrays, count, mine)
        lent.append((mine, batch))
        return batch, mask

    sf._shard = spy_shard
    scale = 1.0 if transform == "views" else 2.0
    out = []
    for batch, mask in sf.batches():
        out.append(batch)
        for b, earlier in enumerate(out):     # every batch so far, again
            assert np.asarray(earlier["y"]).tolist() == \
                list(range(8 * b, 8 * b + 8))
            assert np.asarray(earlier["x"])[:, 0].tolist() == \
                [scale * i for i in range(8 * b, 8 * b + 8)]
    assert len(out) == 9 and len(lent) == 9
    for arrays, batch in lent:
        mine = any(_aliased(leaf, arrays) for leaf in batch.values())
        took = any(arrays[0] is b for b in back)
        assert took == (not mine), "handed back iff no device array is it"
    snap = feed.counters_snapshot()
    assert snap["feed_batch_buffers_new"] + \
        snap["feed_batch_buffers_reused"] == 9
    if transform == "copies":       # nothing on the device is a feed buffer
        assert len(back) == 9
        assert snap["feed_batch_buffers_new"] == 1
        assert snap["feed_batch_buffers_reused"] == 8


def test_a_donated_batch_is_not_handed_back():
    """A step that donates its batch deletes the device arrays: whether the
    transfer out of the host buffers is over can no longer be told, and
    the buffers stay the batch's."""
    feed = _file_feed(_rows(8 * 4))
    back = []
    release = feed.release
    feed.release = lambda arrays: back.append(arrays) or release(arrays)
    sf = ShardedFeed(feed, build_mesh(), global_batch_size=8, prefetch=0,
                     transform=lambda c: {"x": c[0] * 2.0, "y": c[1] + 0})
    hand_back = sf._hand_back
    sf._hand_back = lambda: None        # as if the transfer were under way
    gen = sf.batches()
    batch, _ = next(gen)
    assert len(sf._lent) == 1 and not back
    for leaf in batch.values():
        leaf.delete()                   # what donation leaves behind
    sf._hand_back = hand_back
    later, _ = next(gen)                # looks at the lent batch again
    assert not sf._lent and len(back) == 1      # the second one alone
    assert np.asarray(later["y"]).tolist() == list(range(8, 16))
    assert feed.counters_snapshot()["feed_batch_buffers_reused"] == 0
    gen.close()
    feed.terminate()


def test_terminate_with_buffers_lent_out_does_not_hang():
    import time

    feed = _file_feed(_rows(8 * 64), num_epochs=1000, shuffle_buffer=16)
    sf = ShardedFeed(feed, build_mesh(), global_batch_size=8, prefetch=2,
                     transform=lambda c: {"x": c[0][:, :2], "y": c[1]})
    gen = sf.batches()
    held = [next(gen) for _ in range(3)]    # views: their buffers stay lent
    t0 = time.time()
    sf.terminate()
    assert time.time() - t0 < 10
    t = sf._prefetch_thread
    assert t is not None and not t.is_alive()
    assert feed.should_stop() and all(not r.is_alive() for r in feed._threads)
    for batch, _ in held:                   # and still hold their rows
        y = np.asarray(batch["y"])
        assert np.asarray(batch["x"])[:, 0].tolist() == y.tolist()
    gen.close()


def test_a_feed_without_the_method_is_skipped():
    class Plain(object):
        def __init__(self):
            self.left = 3

        def should_stop(self):
            return self.left == 0

        def next_batch_arrays(self, n):
            self.left -= 1
            return np.full((n, 2), float(self.left), np.float32), n

        def interrupt(self):
            pass

    sf = ShardedFeed(Plain(), build_mesh(), global_batch_size=8, prefetch=0)
    got = [float(np.asarray(b)[0, 0]) for b, _ in sf.batches()]
    assert got == [2.0, 1.0, 0.0]


def test_the_grouped_paths_hand_back_only_what_the_device_path_shards(mgr):
    for assembly, want in (("device", 6), ("host", 0)):
        _fill(mgr, _rows(8 * 6))
        feed = DataFeed(mgr)
        sf = ShardedFeed(feed, build_mesh(), global_batch_size=8,
                         transform=lambda c: {"x": c[0] + 0.0, "y": c[1] + 0},
                         prefetch=0, group_assembly=assembly)
        ys = []
        for kind, batch, _ in sf.grouped_batches(2):
            assert kind == "multi"
            ys.extend(np.asarray(batch["y"]).reshape(-1).tolist())
        assert ys == list(range(48))
        snap = feed.counters_snapshot()
        assert snap["feed_batch_buffers_new"] + \
            snap["feed_batch_buffers_reused"] == 6
        assert snap["feed_batch_buffers_reused"] == max(want - 1, 0)
