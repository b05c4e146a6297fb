"""FileFeed (FILES-mode input pipeline) tests: TFRecord round trip, epochs,
shuffle coverage, ShardedFeed composition, early terminate."""

import numpy as np
import pytest

from tensorflowonspark_tpu import data as data_mod
from tensorflowonspark_tpu import dfutil
from tensorflowonspark_tpu.parallel import build_mesh
from tensorflowonspark_tpu.parallel.infeed import ShardedFeed


@pytest.fixture
def shards(tmp_path):
    rows = dfutil.Rows(
        [{"id": i, "val": float(i) * 0.5} for i in range(100)],
        schema={"id": "int64", "val": "float32"},
    )
    out = str(tmp_path / "tfr")
    dfutil.save_as_tfrecords(rows, out, num_shards=4)
    return out


def _ids(arrays_batches):
    out = []
    for arrays, count in arrays_batches:
        out.extend(int(v) for v in np.asarray(arrays["id"])[:count])
    return out


def _drain(feed, batch_size=16):
    batches = []
    while not feed.should_stop():
        arrays, count = feed.next_batch_arrays(batch_size)
        if count == 0:
            break
        batches.append((arrays, count))
    return batches


class TestFileFeed:
    def test_reads_all_rows_once(self, shards):
        feed = data_mod.FileFeed(data_mod.list_shards(shards), shard=False)
        batches = _drain(feed)
        ids = _ids(batches)
        assert sorted(ids) == list(range(100))
        # columnar dict with both schema fields
        assert set(batches[0][0].keys()) == {"id", "val"}

    def test_epochs_repeat_rows(self, shards):
        feed = data_mod.FileFeed(data_mod.list_shards(shards), shard=False,
                                 num_epochs=3)
        ids = _ids(_drain(feed))
        assert len(ids) == 300
        assert sorted(set(ids)) == list(range(100))
        assert all(ids.count(i) == 3 for i in (0, 42, 99))

    def test_shuffle_covers_all_rows(self, shards):
        feed = data_mod.FileFeed(data_mod.list_shards(shards), shard=False,
                                 shuffle_buffer=32, seed=7)
        ids = _ids(_drain(feed))
        assert sorted(ids) == list(range(100))
        unshuffled = _ids(_drain(data_mod.FileFeed(
            data_mod.list_shards(shards), shard=False)))
        assert ids != unshuffled  # vanishingly unlikely to match

    def test_partial_final_batch_and_should_stop(self, shards):
        feed = data_mod.FileFeed(data_mod.list_shards(shards), shard=False)
        batches = _drain(feed, batch_size=30)
        assert [c for _, c in batches] == [30, 30, 30, 10]
        assert feed.should_stop()

    def test_sharded_feed_composition(self, shards):
        """ShardedFeed (device transfer + padding + consensus) composes on
        FileFeed unchanged — the FILES-mode equivalent of the SPARK plane."""
        mesh = build_mesh()
        feed = data_mod.FileFeed(data_mod.list_shards(shards), shard=False)
        sf = ShardedFeed(
            feed, mesh, global_batch_size=16,
            transform=lambda a: {"id": np.asarray(a["id"], np.int32)})
        out = list(sf.batches())
        assert len(out) == 7  # 6 full + padded 4-row tail
        assert int(np.asarray(out[-1][1]).sum()) == 4
        total = sum(int(np.asarray(m).sum()) for _, m in out)
        assert total == 100

    def test_grouped_batches_composition(self, shards):
        mesh = build_mesh()
        feed = data_mod.FileFeed(data_mod.list_shards(shards), shard=False)
        sf = ShardedFeed(
            feed, mesh, global_batch_size=16,
            transform=lambda a: {"id": np.asarray(a["id"], np.int32)})
        kinds = [k for k, _, _ in sf.grouped_batches(3)]
        # 6 full batches -> 2 groups of 3; the 4-row tail arrives single
        assert kinds == ["multi", "multi", "single"]

    def test_terminate_early_no_hang(self, shards):
        feed = data_mod.FileFeed(data_mod.list_shards(shards), shard=False,
                                 num_epochs=50, queue_size=2)
        feed.next_batch_arrays(8)
        import time

        t0 = time.time()
        feed.terminate()
        assert time.time() - t0 < 10
        assert feed.should_stop()

    def test_process_sharding_splits_files(self):
        files = ["a", "b", "c", "d", "e"]
        s0 = data_mod.shard_for_process(files, 0, 2)
        s1 = data_mod.shard_for_process(files, 1, 2)
        assert s0 == ["a", "c", "e"] and s1 == ["b", "d"]
        # fewer files than processes: everyone reads everything (warned)
        assert data_mod.shard_for_process(["a"], 3, 8) == ["a"]

    def test_reader_error_propagates(self):
        def bad_reader(path):
            raise RuntimeError("corrupt shard " + path)
            yield  # pragma: no cover — marks this as a generator

        feed = data_mod.FileFeed(["x"], row_reader=bad_reader, shard=False)
        with pytest.raises(RuntimeError, match="corrupt shard"):
            _drain(feed)


class TestLMReaders:
    def test_byte_lm_reader_packs_and_covers(self, tmp_path):
        p = tmp_path / "doc.txt"
        payload = bytes(range(256)) * 5  # 1280 bytes
        p.write_bytes(payload)
        feed = data_mod.FileFeed([str(p)],
                                 row_reader=data_mod.byte_lm_reader(100),
                                 shard=False)
        rows = []
        while not feed.should_stop():
            arrays, count = feed.next_batch_arrays(4)
            if count == 0:
                break
            rows.extend(np.asarray(arrays["tokens"])[:count])
        assert len(rows) == 12  # 1280 // 100, tail dropped
        got = b"".join(bytes(r.astype(np.uint8)) for r in rows)
        assert got == payload[:1200]  # exact byte stream, in order

    def test_packed_lm_reader_concatenates_documents(self, tmp_path):
        from tensorflowonspark_tpu import example_proto, tfrecord

        path = str(tmp_path / "toks.tfrecord")
        with tfrecord.TFRecordWriter(path) as w:
            for doc in ([1, 2, 3], [4, 5], [6, 7, 8, 9]):
                w.write(example_proto.encode_example(
                    {"tokens": ("int64", doc)}))
        feed = data_mod.FileFeed(
            [path], row_reader=data_mod.packed_lm_reader(4, eos_id=0),
            shard=False)
        rows = []
        while not feed.should_stop():
            arrays, count = feed.next_batch_arrays(8)
            if count == 0:
                break
            rows.extend(np.asarray(arrays["tokens"])[:count])
        # stream: 1 2 3 0 4 5 0 6 7 8 9 0 -> rows of 4
        assert [r.tolist() for r in rows] == [
            [1, 2, 3, 0], [4, 5, 0, 6], [7, 8, 9, 0]]


def test_sharded_feed_sharding_override(shards):
    """A PartitionSpec(("data",), "seq") override shards 2-d leaves over
    both axes, truncates for 1-d leaves, and keeps the mask batch-only."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from tensorflowonspark_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(data=4, seq=2),
                               keep_trivial_axes=True)
    feed = data_mod.FileFeed(data_mod.list_shards(shards), shard=False)
    override = NamedSharding(mesh, PartitionSpec(("data",), "seq"))
    sf = ShardedFeed(
        feed, mesh, global_batch_size=8, sharding=override, prefetch=0,
        transform=lambda a: {
            "tok": np.tile(np.asarray(a["id"], np.int32)[:, None], (1, 16)),
            "label": np.asarray(a["id"], np.int32)})
    batch, mask = next(sf.batches())
    assert batch["tok"].sharding.spec == PartitionSpec(("data",), "seq")
    assert batch["label"].sharding.spec == PartitionSpec(("data",))
    assert mask.sharding.spec == PartitionSpec(("data",))
    assert batch["tok"].shape == (8, 16)


def test_file_order_reshuffles_each_epoch(tmp_path):
    """With shuffling on, epochs visit files in different orders (tf.data
    reshuffle_each_iteration at file level); coverage stays exact."""
    import json

    files = []
    for i in range(6):
        p = tmp_path / ("f%d" % i)
        p.write_text(json.dumps(i))
        files.append(str(p))

    def reader(path):
        yield {"v": json.load(open(path))}

    feed = data_mod.FileFeed(files, row_reader=reader, shard=False,
                             num_epochs=4, reader_threads=1,
                             shuffle_buffer=1, seed=3)
    vals = []
    while not feed.should_stop():
        arrays, count = feed.next_batch_arrays(100)
        if count == 0:
            break
        vals.extend(int(v) for v in np.asarray(arrays["v"]))
    assert len(vals) == 24
    assert sorted(vals) == sorted(list(range(6)) * 4)
    epochs = [vals[i * 6:(i + 1) * 6] for i in range(4)]
    # the reservoir is tiny (1), so order ~= file order: epochs must differ
    assert len({tuple(e) for e in epochs}) > 1, epochs


class TestProcessPoolFeed:
    """Pool-specific protocol tests (decode-shaped tests live in
    test_imagenet_input.py): end-marker delivery under backpressure and
    worker shutdown on the error path."""

    @pytest.fixture
    def int_shards(self, tmp_path):
        rows = dfutil.Rows([{"id": i} for i in range(300)],
                           schema={"id": "int64"})
        out = str(tmp_path / "tfr")
        dfutil.save_as_tfrecords(rows, out, num_shards=3)
        return data_mod.list_shards(out)

    def test_end_marker_survives_full_queue(self, int_shards):
        """Workers must deliver their end markers even when the consumer
        stalls long enough to fill every queue (block_rows=1 makes 300
        blocks against a 2-block mp queue + 64-block parent queue)."""
        import threading
        import time as time_mod

        feed = data_mod.ProcessPoolFeed(int_shards, num_procs=2,
                                        shard=False, block_rows=1,
                                        queue_blocks=2)
        feed._ensure_started()
        # stall until both workers have read everything and are parked on
        # (or past) their final put
        deadline = time_mod.time() + 60
        while any(p.is_alive() for p in feed._procs):
            if time_mod.time() > deadline:
                break  # backpressure keeps them alive; drain will finish them
            time_mod.sleep(0.2)
        got = []
        done = threading.Event()

        def drain():
            while not feed.should_stop():
                arrays, count = feed.next_batch_arrays(32)
                if count == 0:
                    break
                got.extend(int(v) for v in arrays["id"][:count])
            done.set()

        t = threading.Thread(target=drain, daemon=True)
        t.start()
        assert done.wait(timeout=60), \
            "consumer hung at end of data: end marker lost"
        assert sorted(got) == list(range(300))
        feed.terminate()

    def test_error_path_stops_surviving_workers(self, tmp_path):
        """A worker error must stop the OTHER workers too (forwarder sets
        the stop event), not leave them spinning against a full queue."""
        rows = dfutil.Rows([{"id": i} for i in range(100)],
                           schema={"id": "int64"})
        good = str(tmp_path / "good")
        dfutil.save_as_tfrecords(rows, good, num_shards=1)
        bad = tmp_path / "bad.tfrecord"
        bad.write_bytes(b"garbage that is not a tfrecord")
        files = [str(bad)] + data_mod.list_shards(good)
        feed = data_mod.ProcessPoolFeed(files, num_procs=2, shard=False,
                                        num_epochs=200, block_rows=4,
                                        queue_blocks=2)
        with pytest.raises(IOError):
            while True:
                _, count = feed.next_batch_arrays(8)
                if count == 0:
                    break
        for p in feed._procs:
            p.join(timeout=30)
            assert not p.is_alive(), "surviving worker not stopped on error"
        feed.terminate()


# -- whose memory a batch is (ISSUE 45) ---------------------------------------

def _kind_rows(kind, n=150):
    """``n`` rows of one of the row shapes FileFeed takes."""
    def x(i):
        return np.full((3, 2), i, np.float32)

    if kind == "dict":
        return [{"x": x(i), "id": i, "w": i * 0.5} for i in range(n)]
    if kind == "tuple":
        return [(x(i), i) for i in range(n)]
    if kind == "single":
        return [x(i) for i in range(n)]
    if kind == "scalar":
        return list(range(n))
    if kind == "lists":     # a list row is one vector, not fields
        return [[float(i), i + 0.5] for i in range(n)]
    assert kind == "widening"   # python numbers change kind mid-batch
    return [{"id": i if i % 16 < 11 else i + 0.5,
             "s": "r" * (1 + i % 7), "x": x(i)} for i in range(n)]


def _kind_dtypes(kind):
    return {"dict": {"x": np.float16, "id": np.int32},
            "tuple": (np.float64, np.int16), "single": np.float16,
            "scalar": np.float32, "lists": np.float32,
            "widening": {"id": np.float32, "x": np.int32}}[kind]


def _kind_feed(cls, kind, shuffle, n=150, **kw):
    files = ["a", "b", "c"]

    def reader(path):       # a closure: the pool's workers get it by value
        rows = _kind_rows(kind, n)
        at = files.index(path)
        return iter(rows[at * n // 3:(at + 1) * n // 3])

    if cls is data_mod.ProcessPoolFeed:
        kw.update(num_procs=1, block_rows=16)
    else:
        kw.update(reader_threads=1)
    return cls(files, row_reader=reader, shard=False, shuffle_buffer=shuffle,
               seed=11, **kw)


def _parent_batches(feed, batch_size, dtypes):
    """What the parent's ``next_batch_arrays`` gave, its loop to the letter:
    the blocks of ``_next_rows`` cut at ``batch_size``, each through
    ``_columnar``."""
    feed._ensure_started()
    rows, out = [], []
    while True:
        block = feed._next_rows()
        rows.extend(block or [])
        while len(rows) >= batch_size or (block is None and rows):
            out.append(data_mod.FileFeed._columnar(rows[:batch_size], dtypes))
            rows = rows[batch_size:]
        if block is None:
            return out


def _leaves(arrays):
    if isinstance(arrays, dict):
        return list(arrays.values())
    return list(arrays) if isinstance(arrays, tuple) else [arrays]


def _same(got, want):
    assert type(got) is type(want)
    if isinstance(want, dict):
        assert list(got) == list(want)
    assert len(_leaves(got)) == len(_leaves(want))
    for g, w in zip(_leaves(got), _leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert (g == w).all()


_POOL = data_mod.ProcessPoolFeed
_CONTRACT = [(data_mod.FileFeed, kind, cast, shuffle)
             for kind in ("dict", "tuple", "single", "scalar", "lists",
                          "widening")
             for cast in (False, True) for shuffle in (0, 24)]
_CONTRACT += [(_POOL, "dict", False, 0), (_POOL, "tuple", True, 24)]


@pytest.mark.parametrize(
    "cls,kind,cast,shuffle", _CONTRACT,
    ids=["{}-{}-{}-shuffle{}".format(c.__name__, k, "cast" if d else "asis", s)
         for c, k, d, s in _CONTRACT])
def test_batches_are_what_columnar_gives_in_the_parents_order(
        cls, kind, cast, shuffle):
    """Values, shapes, dtypes and row order of every batch are those of the
    parent's ``_columnar`` over the rows ``_next_rows`` emits for the seed,
    whether or not buffers go round, and a batch the caller keeps is never
    written by a later call."""
    dtypes = _kind_dtypes(kind) if cast else None
    oracle = _kind_feed(cls, kind, shuffle)
    want = _parent_batches(oracle, 16, dtypes)
    oracle.terminate()
    assert len(want) == 10 and len(_leaves(want[-1])[0]) == 150 - 9 * 16
    for hand_back in (False, True):
        feed = _kind_feed(cls, kind, shuffle)
        kept = []
        for b, w in enumerate(want):
            assert not feed.should_stop()
            arrays, count = feed.next_batch_arrays(16, dtypes)
            assert count == len(_leaves(w)[0])
            _same(arrays, w)
            if hand_back and b % 2:
                assert feed.release(arrays)
            else:
                kept.append((arrays, w))
        assert feed.should_stop()
        empty, count = feed.next_batch_arrays(16, dtypes)
        assert count == 0 and empty.shape == (0,)
        for arrays, w in kept:      # many calls later
            _same(arrays, w)
        snap = feed.counters_snapshot()
        assert snap["feed_items"] == 150
        assert snap["feed_batch_buffers_new"] + \
            snap["feed_batch_buffers_reused"] == 10
        if not hand_back:
            assert snap["feed_batch_buffers_reused"] == 0
        elif kind != "widening":
            # every second batch went back and held the one after it (a
            # column that widens or lengthens is new memory every time)
            assert snap["feed_batch_buffers_reused"] == 4
        feed.terminate()


@pytest.mark.parametrize("cls", [data_mod.FileFeed, _POOL],
                         ids=["FileFeed", "ProcessPoolFeed"])
def test_a_released_buffer_is_the_next_batchs_memory(cls):
    feed = _kind_feed(cls, "dict", 0, n=16 * 7 + 5)
    kept = [feed.next_batch_arrays(16)[0] for _ in range(2)]   # never back
    lent = feed.next_batch_arrays(16)[0]
    where = {k: v.ctypes.data for k, v in lent.items()}
    for b in range(3, 7):
        assert feed.release(lent)
        assert not feed.release(lent)       # a batch goes back once
        lent, count = feed.next_batch_arrays(16)
        assert count == 16
        assert {k: v.ctypes.data for k, v in lent.items()} == where
        assert lent["id"].tolist() == list(range(16 * b, 16 * b + 16))
    for b, arrays in enumerate(kept):       # four batches later
        assert arrays["id"].tolist() == list(range(16 * b, 16 * b + 16))
        assert arrays["x"][:, 0, 0].tolist() == arrays["id"].tolist()
    assert feed.release(lent)
    # the partial last batch: arrays of its own length, in no one's buffers
    tail, count = feed.next_batch_arrays(16)
    assert count == 5 and feed.should_stop()
    assert all(v.base is None and len(v) == 5 for v in tail.values())
    assert tail["x"].ctypes.data != where["x"]
    snap = feed.counters_snapshot()
    assert snap["feed_batch_buffers_new"] == 4      # three kept, the tail
    assert snap["feed_batch_buffers_reused"] == 4
    feed.terminate()


def test_only_whole_batches_of_the_kind_in_use_are_taken_back():
    from tensorflowonspark_tpu import datafeed

    feed = _kind_feed(data_mod.FileFeed, "tuple", 0)
    (x, y), _ = feed.next_batch_arrays(16)
    assert not feed.release((x[:8], y[:8]))          # views
    assert not feed.release((x, y[:8].copy()))       # not one batch
    assert not feed.release(None)
    assert not feed.release(np.empty((0,)))
    assert feed.release((x, y))
    (small, _), count = feed.next_batch_arrays(8)    # another batch size
    assert count == 8 and not np.shares_memory(small, x)
    assert len(feed._free) == 1                      # nothing asked for it
    other = (np.empty((8, 3, 2), np.float32), np.empty(8, np.int64))
    assert feed.release(other)                       # replaces the old kind
    assert feed._free_key != datafeed._buffers_key([x, y])
    assert len(feed._free) == 1
    (again, _), count = feed.next_batch_arrays(8)
    assert again is other[0]
    # another dtype is another kind: new memory, and its hand-back drops
    # what the list held
    assert feed.release(other)
    (cast, _), count = feed.next_batch_arrays(8, (np.float16, np.int64))
    assert cast.dtype == np.float16 and len(feed._free) == 1
    assert feed.release((cast, _)) and feed._free[0][0] is cast
    # and so is another row shape
    assert feed.release((np.empty((8, 5), np.float16),
                         np.empty(8, np.int64)))
    assert len(feed._free) == 1 and feed._free[0][0].shape == (8, 5)
    (x2, y2), count = feed.next_batch_arrays(8, (np.float16, np.int64))
    assert x2.shape == (8, 3, 2) and y2.tolist() == list(range(40, 48))
    feed.terminate()


def test_a_row_of_another_shape_raises():
    def reader(path):
        for i in range(8):
            yield {"x": np.zeros((3 if i < 5 else 4,), np.float32)}

    feed = data_mod.FileFeed(["a"], row_reader=reader, shard=False)
    with pytest.raises(ValueError):
        feed.next_batch_arrays(8)
    feed.terminate()


def test_the_consumers_phases_sum_to_the_feeds_age():
    import time

    from tensorflowonspark_tpu import datafeed

    def slow(path):
        for i in range(32):
            if i == 16:
                time.sleep(0.2)     # the consumer waits on the queue here
            yield (np.full(4, i, np.float32), i)

    born = time.monotonic()
    feed = data_mod.FileFeed(["a"], row_reader=slow, shard=False)
    feed.BLOCK = 16
    for _ in range(2):
        feed.next_batch_arrays(16)
        time.sleep(0.05)            # away
    snap = feed.counters_snapshot()
    age_us = (time.monotonic() - born) * 1e6
    phases = [snap["feed_{}_us".format(p)] for p in datafeed.FEED_PHASES]
    assert abs(sum(phases) - age_us) < 50e3
    assert snap["feed_read_us"] == 0        # the readers are threads
    assert snap["feed_wait_us"] >= 100e3
    assert snap["feed_away_us"] >= 100e3
    assert snap["feed_items"] == 32
    feed.terminate()
