"""Manager + DataFeed tests (reference ``test/test_TFNode.py``)."""

import threading
import uuid

import numpy as np
import pytest

from tensorflowonspark_tpu import datafeed, manager, marker, shmring, wire
from tensorflowonspark_tpu.datafeed import DataFeed, absolute_path


@pytest.fixture
def mgr():
    m = manager.start(b"test-authkey", ["input", "output", "error"])
    yield m
    m.shutdown()


def _feed(m, items, end_of_feed=True):
    q = m.get_queue("input")
    for item in items:
        q.put(item)
    if end_of_feed:
        q.put(None)


class TestDataFeed:
    def test_full_and_partial_batches(self, mgr):
        # Reference test_TFNode.py:27-58 — partial final batch + end-of-feed.
        _feed(mgr, list(range(10)))
        feed = DataFeed(mgr)
        batch = feed.next_batch(4)
        assert batch == [0, 1, 2, 3]
        assert not feed.should_stop()
        assert feed.next_batch(4) == [4, 5, 6, 7]
        assert feed.next_batch(4) == [8, 9]  # partial: end-of-feed hit
        assert feed.should_stop()

    def test_end_partition_alignment(self, mgr):
        q = mgr.get_queue("input")
        for i in range(3):
            q.put(i)
        q.put(marker.EndPartition())
        for i in range(3, 5):
            q.put(i)
        q.put(None)
        feed = DataFeed(mgr, train_mode=False)
        # batch stops early at the partition boundary (reference TFNode.py:135-140)
        assert feed.next_batch(10) == [0, 1, 2]
        assert feed.next_batch(10) == [3, 4]
        assert feed.should_stop()

    def test_input_mapping_columns(self, mgr):
        _feed(mgr, [(1, "a"), (2, "b")])
        feed = DataFeed(mgr, input_mapping={"col_x": "x", "col_y": "y"})
        batch = feed.next_batch(2)
        # columns keyed by tensor name, ordered by sorted column name
        assert batch == {"x": [1, 2], "y": ["a", "b"]}

    def test_next_batch_arrays(self, mgr):
        _feed(mgr, [([1.0, 2.0], 3), ([4.0, 5.0], 6)])
        feed = DataFeed(mgr, input_mapping={"a_features": "x", "b_label": "y"})
        arrays, count = feed.next_batch_arrays(2)
        assert count == 2
        assert arrays["x"].shape == (2, 2)
        assert arrays["y"].tolist() == [3, 6]

    def test_batch_results_roundtrip(self, mgr):
        feed = DataFeed(mgr, train_mode=False)
        feed.batch_results([10, 20, 30])
        out = mgr.get_queue("output")
        chunk = out.get()  # whole batch travels as one Chunk
        assert isinstance(chunk, marker.Chunk)
        assert chunk.items == [10, 20, 30]

    def test_chunked_feed_transparent(self, mgr):
        # Feeders send Chunk blocks; consumers still see items, and markers
        # (EndPartition / None) keep their alignment semantics.
        q = mgr.get_queue("input")
        q.put(marker.Chunk([0, 1, 2]))
        q.put(marker.Chunk([3, 4]))
        q.put(marker.EndPartition())
        q.put(marker.Chunk([5, 6]))
        q.put(None)
        feed = DataFeed(mgr)
        assert feed.next_batch(4) == [0, 1, 2, 3]
        assert feed.next_batch(4) == [4]       # stops at partition boundary
        assert feed.next_batch(4) == [5, 6]    # then end-of-feed
        assert feed.should_stop()

    def test_terminate_drains(self, mgr):
        _feed(mgr, list(range(50)))
        feed = DataFeed(mgr)
        feed.next_batch(5)
        feed.terminate()
        assert mgr.get("state") == "terminating"
        q = mgr.get_queue("input")
        assert q.qsize() == 0  # drained through the end-of-feed marker

    def test_terminate_survives_dead_manager(self, mgr):
        # Cluster shutdown can kill the manager while (or just before) a
        # node drains in terminate(); a dead manager means there is
        # nothing left to drain — terminate must finish quietly, not
        # surface EOFError/BrokenPipeError as a user-code failure.  The
        # feed must hold a CONNECTED proxy (the executor's view) whose
        # server dies under it — that's the production shape of the race.
        # (The fixture's teardown shutdown is a no-op second Finalize.)
        client = manager.connect(mgr.address, b"test-authkey")
        _feed(mgr, list(range(10)))
        feed = DataFeed(client)
        feed.next_batch(2)
        mgr.shutdown()
        feed.terminate()  # must not raise

    def test_terminate_survives_manager_dying_mid_drain(self, mgr):
        # Same race one window later: the pre-loop calls succeed, then the
        # manager dies under the drain loop's queue.get.
        _feed(mgr, list(range(5)), end_of_feed=False)
        feed = DataFeed(mgr)
        feed.next_batch(2)

        class _DyingQueue:
            def __init__(self, inner, mgr_to_kill):
                self._inner, self._mgr = inner, mgr_to_kill

            def get(self, *a, **k):
                self._mgr.shutdown()
                raise EOFError  # what the dead proxy raises

            def task_done(self):
                pass

        real_get_queue = mgr.get_queue
        mgr.get_queue = lambda name: _DyingQueue(real_get_queue(name), mgr)
        feed.terminate()  # must not raise


class TestManager:
    def test_kv_state(self, mgr):
        mgr.set("state", "running")
        assert mgr.get("state") == "running"

    def test_connect_local(self, mgr):
        m2 = manager.connect(mgr.address, b"test-authkey")
        m2.get_queue("input").put("hello")
        assert mgr.get_queue("input").get() == "hello"

    def test_remote_mode_tcp(self):
        m = manager.start(b"remote-key", ["control"], mode="remote")
        host, port = m.address
        assert isinstance(port, int) and port > 0
        m2 = manager.connect(("127.0.0.1", port), b"remote-key")
        m2.get_queue("control").put(None)
        assert m.get_queue("control").get() is None
        m.shutdown()


class TestAbsolutePath:
    """Path normalization matrix (reference ``test/test_TFNode.py:8-25``)."""

    def _ctx(self, default_fs, working_dir="/wd"):
        return type("MockContext", (), {
            "default_fs": default_fs, "working_dir": working_dir})()

    def test_schemes_passthrough(self):
        ctx = self._ctx("file://")
        for p in ("file:///tmp/x", "hdfs://nn/x", "gs://bucket/x",
                  "viewfs://cl/x", "s3://b/x"):
            assert absolute_path(ctx, p) == p

    def test_absolute_local(self):
        ctx = self._ctx("file://")
        assert absolute_path(ctx, "/tmp/x") == "file:///tmp/x"

    def test_relative_local_uses_working_dir(self):
        ctx = self._ctx("file://", working_dir="/wd")
        assert absolute_path(ctx, "model") == "file:///wd/model"

    def test_relative_hdfs_user_home(self):
        import getpass

        ctx = self._ctx("hdfs://namenode:8020")
        assert absolute_path(ctx, "model") == \
            "hdfs://namenode:8020/user/{}/model".format(getpass.getuser())

    def test_absolute_on_hdfs_fs(self):
        ctx = self._ctx("hdfs://nn:8020")
        assert absolute_path(ctx, "/data/x") == "/data/x"


class TestColumnarPlane:
    """The columnar data plane: ColChunk packing at the feeder, zero-object
    consumption in next_batch_arrays, row compat in next_batch."""

    def test_pack_columnar_tuple_rows(self):
        import numpy as np

        block = [(np.arange(4, dtype=np.float32) + i, i) for i in range(6)]
        ck = marker.pack_columnar(block)
        assert isinstance(ck, marker.ColChunk)
        assert ck.count == 6 and ck.tuple_rows
        assert ck.columns[0].shape == (6, 4)
        assert ck.columns[1].tolist() == list(range(6))
        img, lab = ck.row(2)
        assert lab == 2 and img.tolist() == [2.0, 3.0, 4.0, 5.0]

    def test_pack_columnar_vector_list_rows(self):
        # A [1.0, 2.0] list row is a length-2 vector, not two fields.
        ck = marker.pack_columnar([[1.0, 2.0], [3.0, 4.0]])
        assert ck.count == 2 and not ck.tuple_rows
        assert ck.columns[0].shape == (2, 2)

    def test_pack_columnar_ragged_falls_back(self):
        import numpy as np

        assert marker.pack_columnar(
            [(np.zeros(3),), (np.zeros(4),)]) is None
        assert marker.pack_columnar([]) is None

    def test_next_batch_unpacks_colchunk_rows(self, mgr):
        import numpy as np

        q = mgr.get_queue("input")
        q.put(marker.pack_columnar([(np.full(2, i, np.float32), i)
                                    for i in range(5)]))
        q.put(None)
        feed = DataFeed(mgr)
        batch = feed.next_batch(3)
        assert [int(lab) for _, lab in batch] == [0, 1, 2]
        batch = feed.next_batch(3)
        assert [int(lab) for _, lab in batch] == [3, 4]
        assert feed.should_stop()

    def test_next_batch_arrays_columnar_native(self, mgr):
        import numpy as np

        q = mgr.get_queue("input")
        for start in (0, 4):
            q.put(marker.pack_columnar(
                [(np.full(3, i, np.float32), i) for i in range(start, start + 4)]))
        q.put(None)
        feed = DataFeed(mgr)
        arrays, count = feed.next_batch_arrays(6)  # spans chunk boundary
        assert count == 6
        x, y = arrays
        assert x.shape == (6, 3) and y.tolist() == [0, 1, 2, 3, 4, 5]
        arrays, count = feed.next_batch_arrays(6)  # partial tail + end of feed
        assert count == 2
        assert arrays[1].tolist() == [6, 7]
        assert feed.should_stop()

    def test_next_batch_arrays_mixed_chunk_kinds(self, mgr):
        import numpy as np

        q = mgr.get_queue("input")
        q.put(marker.pack_columnar([(np.zeros(2, np.float32), 0),
                                    (np.ones(2, np.float32), 1)]))
        q.put(marker.Chunk([(np.full(2, 2.0, np.float32), 2)]))  # object chunk
        q.put((np.full(2, 3.0, np.float32), 3))                  # loose item
        q.put(None)
        feed = DataFeed(mgr, input_mapping={"a_img": "x", "b_lab": "y"})
        arrays, count = feed.next_batch_arrays(10)
        assert count == 4
        assert arrays["x"].shape == (4, 2)
        assert arrays["y"].tolist() == [0, 1, 2, 3]

    def test_next_batch_arrays_dtype_cast(self, mgr):
        import numpy as np

        q = mgr.get_queue("input")
        q.put(marker.pack_columnar([(np.zeros(2, np.uint8), 1)] * 3))
        q.put(None)
        feed = DataFeed(mgr)
        (x, y), count = feed.next_batch_arrays(3, dtypes=[np.float32, np.int32])
        assert x.dtype == np.float32 and y.dtype == np.int32

    def test_end_partition_respected_on_arrays_path(self, mgr):
        import numpy as np

        q = mgr.get_queue("input")
        q.put(marker.pack_columnar([(np.zeros(1, np.float32), i)
                                    for i in range(3)]))
        q.put(marker.EndPartition())
        q.put(marker.pack_columnar([(np.zeros(1, np.float32), i)
                                    for i in range(3, 5)]))
        q.put(None)
        feed = DataFeed(mgr, train_mode=False)
        _, count = feed.next_batch_arrays(10)
        assert count == 3                       # stops at partition boundary
        arrays, count = feed.next_batch_arrays(10)
        assert count == 2 and arrays[1].tolist() == [3, 4]


# ---------------------------------------------------------------------------
# The array path builds a batch in one buffer a column: every row is copied
# once, a ring chunk's straight from the in-ring views (ISSUE 33).
# ---------------------------------------------------------------------------

ring_required = pytest.mark.skipif(not shmring.available(),
                                   reason="native shm ring unavailable")


@pytest.fixture
def ring():
    name = "/tfos_test_feed_{}".format(uuid.uuid4().hex[:8])
    r = shmring.Ring.create_or_attach(name, 4 << 20)
    shmring._rings[name] = r     # what DataFeed's get_ring finds
    yield r
    shmring._rings.pop(name, None)
    r.detach(unlink=True)


_KINDS = {
    # kind: (rows from labels, input_mapping, dtypes)
    "tuple": (lambda i: (np.full(3, i, np.float32), i), None, None),
    "single": (lambda i: np.full(3, i, np.float32), None, None),
    "mapping": (lambda i: (np.full(3, i, np.float32), i),
                {"a_img": "x", "b_lab": "y"}, None),
    "cast": (lambda i: (np.full(3, i, np.uint8), i), None,
             [np.float32, np.int32]),
    "mapping_cast": (lambda i: (np.full(3, i, np.uint8), i),
                     {"a_img": "x", "b_lab": "y"}, {"y": np.int16}),
}


def _send(mgr, ring, chunk, transport):
    """One ColChunk to the feed: through the ring as a framed record with
    its token, or in the queue itself."""
    q = mgr.get_queue("input")
    if transport == "ring":
        assert ring.put_vectored(wire.encode_chunk(chunk), timeout_secs=5)
        q.put(marker.ShmChunk(ring.name, chunk.count, fmt=wire.WIRE_COLV1))
    else:
        q.put(chunk)


def _same(got, want):
    if isinstance(want, dict):
        assert sorted(got) == sorted(want)
        for k in want:
            _same(got[k], want[k])
    elif isinstance(want, tuple):
        assert isinstance(got, tuple) and len(got) == len(want)
        for g, w in zip(got, want):
            _same(g, w)
    else:
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, want)


@ring_required
@pytest.mark.parametrize("transport", ["ring", "queue"])
@pytest.mark.parametrize("kind", sorted(_KINDS))
@pytest.mark.parametrize("chunk,batch", [(5, 8), (7, 8), (32, 8),
                                         (5, 256), (7, 256), (32, 256)])
def test_straddling_chunks_give_what_assemble_columns_gave(
        mgr, ring, chunk, batch, kind, transport):
    """Chunks that do not divide the batch: the same rows in the same order,
    dtypes and shapes as the parent's slices-then-concatenate."""
    make, mapping, dtypes = _KINDS[kind]
    total = 2 * batch + batch // 2 + 3
    rows = [make(i) for i in range(total)]
    for at in range(0, total, chunk):
        _send(mgr, ring, marker.pack_columnar(rows[at:at + chunk]),
              transport)
    mgr.get_queue("input").put(None)
    feed = DataFeed(mgr, input_mapping=mapping)
    whole = marker.pack_columnar(rows)
    at = 0
    while not feed.should_stop():
        got, count = feed.next_batch_arrays(batch, dtypes=dtypes)
        want = datafeed.assemble_columns(
            [tuple(c[at:at + batch] for c in whole.columns)],
            whole.tuple_rows, dtypes, feed.input_tensors)
        assert count == min(batch, total - at)
        _same(got, want)
        at += count
    assert at == total
    fmt = "colv1" if transport == "ring" else "queue"
    assert feed.wire_formats == {fmt: -(-total // chunk)}
    if transport == "ring":
        assert ring.consumes == -(-total // chunk)
    mgr.get_queue("input").join()   # every chunk was acked


@ring_required
def test_a_compressed_column_is_copied_in_from_its_own_buffer(mgr, ring):
    q = mgr.get_queue("input")
    cols = (np.tile(np.arange(64, dtype=np.float32), (12, 4)),
            np.arange(12, dtype=np.int64))
    info = {}
    frame = wire.frame_bytes(cols, 12, True, codec="zlib", stats=info)
    assert info["cols_compressed"] == 1
    for _ in range(2):
        assert ring.put_bytes(frame, timeout_secs=5)
        q.put(marker.ShmChunk(ring.name, 12, fmt=wire.WIRE_COLV1))
    q.put(None)
    feed = DataFeed(mgr)
    (x, y), count = feed.next_batch_arrays(16)
    assert count == 16
    np.testing.assert_array_equal(x, np.concatenate([cols[0], cols[0][:4]]))
    assert y.tolist() == list(range(12)) + [0, 1, 2, 3]
    (x, y), count = feed.next_batch_arrays(16)
    assert count == 8 and y.tolist() == list(range(4, 12))
    np.testing.assert_array_equal(x, cols[0][4:])


@ring_required
def test_every_kind_of_chunk_mixed_into_one_batch(mgr, ring):
    def rows(lo, hi):
        return [(np.full(2, i, np.float32), i) for i in range(lo, hi)]

    q = mgr.get_queue("input")
    _send(mgr, ring, marker.pack_columnar(rows(0, 3)), "ring")
    _send(mgr, ring, marker.pack_columnar(rows(3, 5)), "queue")
    q.put(marker.Chunk(rows(5, 7)))                       # object chunk
    q.put(rows(7, 8)[0])                                  # loose item
    assert ring.put(marker.pack_columnar(rows(8, 10)), timeout_secs=5)
    q.put(marker.ShmChunk(ring.name, 2))                  # pickled in-ring
    _send(mgr, ring, marker.pack_columnar(rows(10, 14)), "ring")
    q.put(None)
    feed = DataFeed(mgr, input_mapping={"a_img": "x", "b_lab": "y"})
    arrays, count = feed.next_batch_arrays(12)
    assert count == 12 and arrays["y"].tolist() == list(range(12))
    np.testing.assert_array_equal(
        arrays["x"], np.repeat(np.arange(12, dtype=np.float32), 2)
        .reshape(12, 2))
    arrays, count = feed.next_batch_arrays(12)
    assert count == 2 and arrays["y"].tolist() == [12, 13]
    assert feed.wire_formats == {"colv1": 2, "queue": 2, "pickle": 1}


@ring_required
def test_python_numbers_that_change_kind_mid_batch_widen_the_column(mgr):
    # what np.concatenate did: ints then floats give floats, nothing cut
    q = mgr.get_queue("input")
    q.put(marker.Chunk([(1, 2), (3, 4)]))
    q.put(marker.Chunk([(0.5, 6)]))
    q.put(None)
    (x, y), count = DataFeed(mgr).next_batch_arrays(8)
    assert count == 3 and x.dtype == np.float64 and y.dtype == np.int64
    assert x.tolist() == [1.0, 3.0, 0.5] and y.tolist() == [2, 4, 6]


@ring_required
@pytest.mark.parametrize("end", ["end_partition", "end_of_feed", "interrupt"])
def test_a_batch_cut_short_returns_its_first_rows(mgr, ring, end):
    rows = [(np.full(2, i, np.float32), i) for i in range(11)]
    q = mgr.get_queue("input")
    _send(mgr, ring, marker.pack_columnar(rows[:6]), "ring")
    feed = DataFeed(mgr, train_mode=False)
    if end == "end_partition":
        q.put(marker.EndPartition())
    elif end == "end_of_feed":
        q.put(None)
    else:
        feed._poll_secs = 0.05
        threading.Timer(0.3, feed.interrupt).start()
    (x, y), count = feed.next_batch_arrays(8)
    assert count == 6 and y.tolist() == list(range(6))
    assert x.shape == (6, 2) and x[:, 0].tolist() == list(range(6))
    assert feed.should_stop() == (end == "end_of_feed")
    if end == "end_partition":
        _send(mgr, ring, marker.pack_columnar(rows[6:]), "ring")
        q.put(None)
        (x, y), count = feed.next_batch_arrays(8)
        assert count == 5 and y.tolist() == list(range(6, 11))
    assert feed.counters_snapshot()["feed_items"] == \
        (11 if end == "end_partition" else 6)


@ring_required
@pytest.mark.parametrize("transport", ["ring", "queue"])
def test_rows_and_arrays_called_alternately_lose_and_repeat_nothing(
        mgr, ring, transport):
    total = 61
    rows = [(np.full(2, i, np.float32), i) for i in range(total)]
    for at in range(0, total, 7):
        _send(mgr, ring, marker.pack_columnar(rows[at:at + 7]), transport)
    mgr.get_queue("input").put(None)
    feed = DataFeed(mgr)
    seen = []
    sizes = [4, 3, 9, 5, 16, 2]      # 9 and 16 leave a chunk's tail ahead
    turn = 0
    while not feed.should_stop():
        n = sizes[turn % len(sizes)]
        if turn % 2:
            got = feed.next_batch(n)
            assert all(float(img[0]) == lab for img, lab in got)
            seen.extend(int(lab) for _, lab in got)
        else:
            (x, y), count = feed.next_batch_arrays(n)
            assert x[:, 0].tolist() == y.tolist() and len(y) == count
            seen.extend(y.tolist())
        turn += 1
    assert seen == list(range(total))
    assert feed.counters_snapshot()["feed_items"] == total
    mgr.get_queue("input").join()


def _joins(queue, within):
    """Whether ``queue.join()`` returns within ``within`` seconds."""
    done = threading.Event()
    threading.Thread(target=lambda: (queue.join(), done.set()),
                     daemon=True).start()
    return done.wait(within)


@ring_required
@pytest.mark.parametrize("transport", ["ring", "queue"])
def test_the_head_of_a_straddling_chunk_leaves_the_chunk_unacked(
        mgr, ring, transport):
    """A consumer that stops mid-chunk must leave the queue un-joined (the
    feeder's error poll then fires), although the ring slot is long free."""
    rows = [(np.full(2, i, np.float32), i) for i in range(12)]
    q = mgr.get_queue("input")
    for at in (0, 6):
        _send(mgr, ring, marker.pack_columnar(rows[at:at + 6]), transport)
    feed = DataFeed(mgr)
    (_, y), count = feed.next_batch_arrays(8)
    assert y.tolist() == list(range(8))
    if transport == "ring":
        assert ring.consumes == 2     # memory: both slots are the feeder's
    assert not _joins(q, 0.5)         # the guarantee: chunk 2 is not acked
    (_, y), count = feed.next_batch_arrays(4)
    assert y.tolist() == [8, 9, 10, 11]
    assert _joins(q, 5)


@ring_required
def test_a_chunk_of_many_batches_is_acked_with_its_last(mgr, ring):
    rows = [(np.full(2, i, np.float32), i) for i in range(32)]
    q = mgr.get_queue("input")
    _send(mgr, ring, marker.pack_columnar(rows), "ring")
    feed = DataFeed(mgr)
    for b in range(3):
        (_, y), _ = feed.next_batch_arrays(8)
        assert y.tolist() == list(range(8 * b, 8 * b + 8))
        assert ring.consumes == 1 and not _joins(q, 0.2)
    (_, y), _ = feed.next_batch_arrays(8)
    assert y.tolist() == list(range(24, 32)) and _joins(q, 5)


@ring_required
@pytest.mark.parametrize("fault", ["structure", "shape", "count"])
def test_the_ring_slot_is_consumed_once_a_token_when_the_copy_raises(
        mgr, ring, fault):
    q = mgr.get_queue("input")
    good = marker.pack_columnar([(np.full(2, i, np.float32), i)
                                 for i in range(3)])
    _send(mgr, ring, good, "ring")
    if fault == "structure":      # single-value rows after tuple rows
        bad = marker.pack_columnar([np.zeros(2, np.float32)] * 3)
        error, match = ValueError, "inconsistent row structure"
    elif fault == "shape":        # np.concatenate raised here too
        bad = marker.pack_columnar([(np.zeros(5, np.float32), 0)] * 3)
        error, match = ValueError, "inconsistent row structure"
    else:
        bad = good
        error, match = RuntimeError, "desync"
    assert ring.put_vectored(wire.encode_chunk(bad), timeout_secs=5)
    q.put(marker.ShmChunk(ring.name, 3 if fault != "count" else 4,
                          fmt=wire.WIRE_COLV1))
    _send(mgr, ring, good, "ring")
    q.put(None)
    feed = DataFeed(mgr)
    with pytest.raises(error, match=match):
        feed.next_batch_arrays(8)
    assert ring.consumes == 2
    # tokens and records are still 1:1: the record after the bad one reads
    (_, y), count = DataFeed(mgr).next_batch_arrays(8)
    assert count == 3 and y.tolist() == [0, 1, 2] and ring.consumes == 3


# -- whose memory a batch is ------------------------------------------------

def _queue_batches(mgr, n, batch=8, tail=0):
    q = mgr.get_queue("input")
    for b in range(n + bool(tail)):
        q.put(marker.pack_columnar(
            [(np.full(4, b * batch + i, np.float32), b * batch + i)
             for i in range(batch if b < n else tail)]))
    q.put(None)


def test_a_caller_that_never_hands_back_gets_memory_of_its_own(mgr):
    _queue_batches(mgr, 6)
    feed = DataFeed(mgr)
    kept = [feed.next_batch_arrays(8)[0] for _ in range(6)]
    for b, (x, y) in enumerate(kept):
        assert y.tolist() == list(range(8 * b, 8 * b + 8))
        assert x[:, 0].tolist() == y.tolist()
        for x2, y2 in kept[:b]:
            assert not np.shares_memory(x, x2) and not np.shares_memory(y, y2)
    snap = feed.counters_snapshot()
    assert snap["feed_batch_buffers_new"] == 6
    assert snap["feed_batch_buffers_reused"] == 0


def test_handed_back_buffers_hold_later_batches_and_no_others_move(mgr):
    _queue_batches(mgr, 7)
    feed = DataFeed(mgr)
    kept = [feed.next_batch_arrays(8)[0] for _ in range(2)]   # never back
    lent = feed.next_batch_arrays(8)[0]
    where = lent[0].ctypes.data
    for b in range(3, 7):
        assert feed.release(lent)
        assert not feed.release(lent)       # a batch goes back once
        lent, count = feed.next_batch_arrays(8)
        assert count == 8 and lent[0].ctypes.data == where
        assert lent[1].tolist() == list(range(8 * b, 8 * b + 8))
    for b, (x, y) in enumerate(kept):       # four batches later
        assert y.tolist() == list(range(8 * b, 8 * b + 8))
        assert x[:, 0].tolist() == y.tolist()
    snap = feed.counters_snapshot()
    assert snap["feed_batch_buffers_new"] == 3
    assert snap["feed_batch_buffers_reused"] == 4


def test_only_whole_batches_of_the_kind_in_use_are_taken_back(mgr):
    _queue_batches(mgr, 3, tail=5)
    feed = DataFeed(mgr)
    (x, y), _ = feed.next_batch_arrays(8)
    assert not feed.release((x[:4], y[:4]))          # views
    assert not feed.release((x, y[:4].copy()))       # not one batch
    assert not feed.release({"x": x, "y": y})        # no input_mapping here
    assert not feed.release(None)
    assert feed.release((x, y))
    (small, _), count = feed.next_batch_arrays(4)    # another batch size
    assert count == 4 and not np.shares_memory(small, x)
    assert feed.release((np.empty((4, 4), np.float32),
                         np.empty(4, np.int64)))     # replaces the old kind
    assert feed._free_key != datafeed._buffers_key([x, y])
    assert len(feed._free) == 1
    tail, count = feed.next_batch_arrays(4)
    assert count == 4 and tail[1].tolist() == [12, 13, 14, 15]
    (_, y3), count = feed.next_batch_arrays(8)
    assert count == 8 and y3.tolist() == list(range(16, 24))
    # a partial batch's views are not taken either, nor is nothing
    partial, count = feed.next_batch_arrays(8)
    assert count == 5 and partial[1].tolist() == list(range(24, 29))
    assert not feed.release(partial)
    assert feed.should_stop() and not feed.release(np.empty((0,)))


def test_a_mapping_feed_takes_its_dict_back(mgr):
    _queue_batches(mgr, 3)
    feed = DataFeed(mgr, input_mapping={"a_img": "x", "b_lab": "y"})
    first, _ = feed.next_batch_arrays(8)
    assert feed.release(first)
    second, _ = feed.next_batch_arrays(8)
    assert second["x"] is first["x"] and second["y"] is first["y"]
    assert second["y"].tolist() == list(range(8, 16))
